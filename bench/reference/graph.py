"""Graph tables of the reference, built from the edge list alone.

Same semantics as the program's reverse-CSR container: rows are
destination vertices, in-neighbours in edge-list order after a stable
sort by destination; IC probabilities U[0, 0.1] and LT weights (U[0.1,
1] normalised per vertex in float64) drawn in that order from
``numpy.random.default_rng(seed)``.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Tables(NamedTuple):
    nbr: np.ndarray     # int32 [n, d]; -1 pads
    prob: np.ndarray    # float32 [n, d]
    wt: np.ndarray      # float32 [n, d]
    in_deg: np.ndarray  # int64 [n]

    @property
    def n(self) -> int:
        return self.nbr.shape[0]


def tables(src, dst, n: int, seed: int) -> Tables:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    nnz = src.shape[0]
    in_deg = np.bincount(dst, minlength=n)
    start = np.concatenate([[0], np.cumsum(in_deg)[:-1]])
    rng = np.random.default_rng(seed)
    prob = rng.uniform(0.0, 0.1, size=nnz).astype(np.float32)
    raw = rng.uniform(0.1, 1.0, size=nnz)
    row_sum = np.zeros(n)
    np.add.at(row_sum, dst, raw)
    wt = (raw / np.maximum(row_sum[dst], 1e-12)).astype(np.float32)
    d = int(in_deg.max()) if nnz else 0
    slot = np.arange(nnz) - start[dst]
    nbr = np.full((n, d), -1, np.int32)
    p = np.zeros((n, d), np.float32)
    w = np.zeros((n, d), np.float32)
    nbr[dst, slot] = src
    p[dst, slot] = prob
    w[dst, slot] = wt
    return Tables(nbr, p, w, in_deg)


def lt_thresholds(wt) -> np.ndarray:
    """Running sums of each row's LT weights, float32, left to right."""
    return np.cumsum(np.asarray(wt, np.float32), axis=1, dtype=np.float32)
