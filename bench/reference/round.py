"""Reference of one fixed-theta GreediRIS round on one machine.

Samples theta RRR sets in ``chunks`` batches (chunk i keyed
``fold_in(fold_in(key, 0), i)``), orders the vertex rows by the
round's uniform partition ``permutation(fold_in(key, 0x9E37), n)``,
solves greedy max-k-cover over those rows, streams the picks through
the bucketed receiver (lower bound = the first pick's gain), and keeps
the receiver's answer when it covers at least as much as the local
one.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.reference import cover, prng, rrr


class RoundOut(NamedTuple):
    seeds: np.ndarray
    coverage: int
    global_coverage: int
    best_local_coverage: int
    steps: tuple          # BFS steps of each chunk
    near_ties: int        # receiver decisions within 1e-5 of a threshold


def sample_round(tab, key, *, theta: int, chunks: int, model: str,
                 max_steps: int, coin_chunk: int = 32, cumw=None,
                 coin: str = "f32"):
    """The round's sets: (sample ids, vertices, steps per chunk)."""
    key_p = prng.fold_in(key, 0)
    b = theta // chunks
    samples, verts, steps = [], [], []
    for i in range(chunks):
        s, v, st = rrr.chunk_batch(tab, prng.fold_in(key_p, i), b,
                                   model=model, max_steps=max_steps,
                                   coin_chunk=coin_chunk, cumw=cumw,
                                   coin=coin)
        samples.append(s + i * b)
        verts.append(v)
        steps.append(st)
    return np.concatenate(samples), np.concatenate(verts), tuple(steps)


def solve_round(tab, key, samples, verts, *, theta: int, k: int,
                delta: float) -> RoundOut:
    """The round's answer from its sampled sets (``sample_round``)."""
    n = tab.n
    perm = prng.permutation(prng.fold_in(key, 0x9E37), n)
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    inc = cover.Incidence(inv[verts], samples, n, theta)
    rows, gains = cover.greedy(inc, k)
    local_ids = np.where(rows >= 0, perm[np.clip(rows, 0, None)], -1)
    local_cov = inc.coverage(rows)
    thr = cover.thresholds(k, delta, float(gains[0]))
    g_seeds, g_cov, near = cover.stream(inc, local_ids, rows, k, thr)
    seeds = g_seeds if g_cov >= local_cov else local_ids
    return RoundOut(seeds.astype(np.int32), max(g_cov, local_cov), g_cov,
                    local_cov, (), near)


def run_round(tab, key, *, theta: int, chunks: int, k: int, model: str,
              max_steps: int, delta: float, coin_chunk: int = 32,
              cumw=None, coin: str = "f32") -> RoundOut:
    samples, verts, steps = sample_round(
        tab, key, theta=theta, chunks=chunks, model=model,
        max_steps=max_steps, coin_chunk=coin_chunk, cumw=cumw, coin=coin)
    out = solve_round(tab, key, samples, verts, theta=theta, k=k,
                      delta=delta)
    return out._replace(steps=steps)
