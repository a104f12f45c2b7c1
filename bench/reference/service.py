"""Reference of the query service's answers from a resident pool.

The pool holds two halves of ``theta`` sets each, sampled in slabs of
``slab`` sets: slab s of half h keyed ``fold_in(fold_in(fold_in(key,
h), s), salt)`` (salt 1 for the first fill).  A query (k, excluded,
budget) is answered by greedy max-k-cover on half 1 with the excluded
vertices never taken, cut at the first pick whose running coverage
reaches ``ceil(budget * theta / n)``; half 2 validates the seeds, and
the OPIM bounds (Tang et al.) certify the answer.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

from bench.reference import cover, prng, rrr

INT32_MAX = 2 ** 31 - 1


class RefAnswer(NamedTuple):
    seeds: np.ndarray
    k_used: int
    coverage: int
    sigma_lower: float
    sigma_upper: float
    guarantee: float
    certified: bool


def sample_pool(tab, key, *, theta: int, slab: int, model: str,
                max_steps: int, coin_chunk: int = 32, cumw=None,
                coin: str = "f32", salt: int = 1):
    """The two halves as :class:`cover.Incidence` over vertex rows,
    and the number of BFS steps their sampling took."""
    halves, steps = [], 0
    for h in (0, 1):
        kh = prng.fold_in(key, h)
        samples, verts = [], []
        for s in range(theta // slab):
            ks = prng.fold_in(prng.fold_in(kh, s), salt)
            b, v, st = rrr.chunk_batch(tab, ks, slab, model=model,
                                       max_steps=max_steps,
                                       coin_chunk=coin_chunk, cumw=cumw,
                                       coin=coin)
            steps += st
            samples.append(b + s * slab)
            verts.append(v)
        halves.append(cover.Incidence(np.concatenate(verts),
                                      np.concatenate(samples), tab.n,
                                      theta))
    return halves[0], halves[1], steps


def sigma_lower(cov: float, theta: int, n: int, delta: float) -> float:
    a = math.log(1.0 / delta)
    val = (math.sqrt(cov + 2.0 * a / 9.0) - math.sqrt(a / 2.0)) ** 2 \
        - a / 18.0
    return max(val, 0.0) * n / theta


def sigma_upper(cov_ub: float, theta: int, n: int, delta: float) -> float:
    a = math.log(1.0 / delta)
    return (math.sqrt(cov_ub + a / 2.0) + math.sqrt(a / 2.0)) ** 2 \
        * n / theta


class Answerer:
    """Answers queries against one pool.  The unconstrained greedy
    order is computed once: a query whose excluded vertices are not
    among its first k unconstrained picks has exactly those picks
    (each pick was the lowest-numbered best row with or without the
    exclusions); any other query is solved again with them left out."""

    def __init__(self, r1: cover.Incidence, r2: cover.Incidence, *,
                 k_max: int, delta: float = 1.0 / 128.0,
                 alpha: float | None = None):
        self.r1, self.r2 = r1, r2
        self.n, self.theta = r1.num_rows, r1.theta
        self.delta = delta
        self.alpha = 1.0 - 1.0 / math.e if alpha is None else alpha
        self.rows, self.gains = cover.greedy(r1, k_max)

    def answer(self, k: int, excluded=(), budget=None,
               eps: float = 0.3) -> RefAnswer:
        rows, gains = self.rows[:k], self.gains[:k]
        if set(int(e) for e in excluded) & set(int(r) for r in rows):
            rows, gains = cover.greedy(self.r1, k, excluded)
        if budget is None:
            budget_cov = INT32_MAX
        else:
            budget_cov = int(math.ceil(budget * self.theta / self.n))
        reached = np.cumsum(gains) >= budget_cov
        j = int(np.argmax(reached)) + 1 if reached.any() else k
        j = min(j, k)
        seeds = np.where(np.arange(k) < j, rows, -1)
        c1 = float(self.r1.coverage(seeds))
        c2 = float(self.r2.coverage(seeds))
        sig_l = sigma_lower(c2, self.theta, self.n, self.delta)
        sig_u = sigma_upper(c1 / self.alpha, self.theta, self.n,
                            self.delta)
        guar = sig_l / max(sig_u, 1e-9)
        certified = guar >= self.alpha - eps or (
            budget is not None and sig_l >= budget)
        return RefAnswer(seeds.astype(np.int32), int((seeds >= 0).sum()),
                         int(c1), sig_l, sig_u, guar, bool(certified))
