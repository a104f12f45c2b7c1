"""Reference RRR sampling: every set walked on its own.

One batch of ``batch`` sets from key ``kb`` follows the program's
stated stream: BFS step t draws with ``sub_t`` (``k, sub = split(k)``
from ``kb``); under IC the coin of in-edge slot s of vertex v for
sample b is element ``(b, v, s % c)`` of
``uniform(fold_in(sub_t, s // c), (batch, n, c))`` with ``c =
min(d, coin_chunk)``, and the edge fires when the coin is below its
probability; under LT sample b at v draws ``uniform(sub_t, (batch,
n))[b, v]`` and follows the first in-edge whose running weight sum
exceeds it, or none.  A vertex reached again is not expanded again.
The batch stops after ``max_steps`` steps or when no set grew.

Only the coins of edges that a frontier vertex examines are drawn, so
a batch costs the size of its sets, not ``batch * n * d``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import prng

COINS = ("f32", "bf16")


def _bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + np.uint64(0x7FFF) + ((u >> np.uint64(16)) & np.uint64(1)))
    return (u.astype(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)


def _bf16_cumsum(wt):
    """Running sums of the weights, each add rounded to bfloat16."""
    w = _bf16(wt)
    out = np.empty_like(w)
    acc = np.zeros(w.shape[0], np.float32)
    for j in range(w.shape[1]):
        acc = _bf16(acc + w[:, j])
        out[:, j] = acc
    return out


def _uniform(bits, coin: str):
    if coin == "f32":
        return prng.uniform_f32(bits)
    return prng.uniform_bf16(bits)


def sample_batch(tab, roots, kb, *, model: str, max_steps: int,
                 coin_chunk: int = 32, cumw=None, coin: str = "f32"):
    """Walk ``len(roots)`` RRR sets.  Returns ``(sample, vertex, steps)``:
    the visited pairs (int64 arrays) and the number of BFS steps the
    batch took.  ``coin="bf16"`` draws every coin as a bfloat16
    uniform and compares it with the edge's probability, or the
    running weight sum, in bfloat16."""
    if coin not in COINS:
        raise ValueError(f"coin must be one of {COINS}, got {coin!r}")
    n, d = tab.nbr.shape
    batch = len(roots)
    fb = np.arange(batch, dtype=np.int64)
    fv = np.asarray(roots, np.int64)
    visited = np.unique(fb * n + fv)
    if d == 0:
        return fb, fv, 0
    chunk = min(d, coin_chunk)
    prob = tab.prob if coin == "f32" else _bf16(tab.prob)
    if model == "LT":
        if cumw is None:
            raise ValueError("LT needs the running weight sums cumw")
        cumw = np.asarray(cumw, np.float32)
        if coin == "bf16":
            cumw = _bf16_cumsum(tab.wt)
    key = kb
    step = 0
    while fb.size and step < max_steps:
        key, sub = prng.split(key)
        if model == "IC":
            b = np.repeat(fb, d)
            v = np.repeat(fv, d)
            s = np.tile(np.arange(d, dtype=np.int64), fb.size)
            u = tab.nbr[v, s].astype(np.int64)
            ok = u >= 0
            b, v, s, u = b[ok], v[ok], s[ok], u[ok]
            fire = np.zeros(b.size, bool)
            for c in np.unique(s // chunk):
                sel = (s // chunk) == c
                idx = (b[sel] * n + v[sel]) * chunk + s[sel] % chunk
                coins = _uniform(prng.bits32(prng.fold_in(sub, int(c)),
                                             idx), coin)
                fire[sel] = coins < prob[v[sel], s[sel]]
            hit = b[fire] * n + u[fire]
        elif model == "LT":
            r = _uniform(prng.bits32(sub, fb * n + fv), coin)
            chosen = np.sum(r[:, None] >= cumw[fv], axis=1)
            ok = chosen < tab.in_deg[fv]
            u = tab.nbr[fv[ok], chosen[ok]].astype(np.int64)
            hit = fb[ok] * n + u
        else:
            raise ValueError(f"unknown model {model!r}")
        new = np.setdiff1d(np.unique(hit), visited, assume_unique=True)
        visited = np.union1d(visited, new)
        fb, fv = new // n, new % n
        step += 1
    return visited // n, visited % n, step


def chunk_batch(tab, kc, batch: int, **kw):
    """One chunk as the program samples it: ``kr, kb = split(kc)``,
    roots ``randint(kr, (batch,), 0, n)``, the sets walked from kb."""
    kr, kb = prng.split(kc)
    roots = prng.randint(kr, batch, 0, tab.n)
    return sample_batch(tab, roots, kb, **kw)
