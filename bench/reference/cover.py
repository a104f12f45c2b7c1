"""Reference max-k-cover: lazy greedy over explicit sets, and the
bucketed streaming receiver (McGregor-Vu thresholds).

Rows are numbered; a row is the array of sample ids it covers.  The
greedy takes the row of largest marginal gain, the lowest-numbered
among equals, and stops taking once no gain is positive (the remaining
picks are -1 with gain 0).
"""
from __future__ import annotations

import heapq
import math

import numpy as np


class Incidence:
    """Sample ids covered by each row, stored row-sorted (CSR)."""

    def __init__(self, rows, samples, num_rows: int, theta: int):
        order = np.argsort(rows, kind="stable")
        self.samples = np.asarray(samples, np.int64)[order]
        self.ptr = np.searchsorted(np.asarray(rows)[order],
                                   np.arange(num_rows + 1))
        self.num_rows = num_rows
        self.theta = theta

    def row(self, r: int) -> np.ndarray:
        return self.samples[self.ptr[r]:self.ptr[r + 1]]

    def sizes(self) -> np.ndarray:
        return np.diff(self.ptr)

    def coverage(self, rows) -> int:
        rows = [int(r) for r in rows if r >= 0]
        if not rows:
            return 0
        return int(np.unique(np.concatenate([self.row(r)
                                             for r in rows])).size)


def greedy(inc: Incidence, k: int, excluded=()):
    """Greedy max-k-cover.  Returns (rows [k] with -1 pads, gains [k])."""
    covered = np.zeros(inc.theta, bool)
    skip = {int(e) for e in excluded if 0 <= int(e) < inc.num_rows}
    sizes = inc.sizes()
    heap = [(-int(sizes[r]), int(r)) for r in np.nonzero(sizes)[0]
            if int(r) not in skip]
    heapq.heapify(heap)
    rows = np.full(k, -1, np.int64)
    gains = np.zeros(k, np.int64)
    for i in range(k):
        while heap:
            neg, r = heapq.heappop(heap)
            g = int(np.count_nonzero(~covered[inc.row(r)]))
            if not heap or (-g, r) <= heap[0]:
                break
            heapq.heappush(heap, (-g, r))
        else:
            break
        if g <= 0:
            break
        rows[i], gains[i] = r, g
        covered[inc.row(r)] = True
    return rows, gains


def num_buckets(k: int, delta: float) -> int:
    return max(1, math.ceil(math.log(max(k, 2)) / math.log1p(delta)))


def thresholds(k: int, delta: float, lower: float) -> np.ndarray:
    """Admission threshold of each bucket, guess_b / (2k), float32."""
    b = num_buckets(k, delta)
    guesses = np.float32(lower) * np.power(
        np.float32(1.0 + delta), np.arange(b, dtype=np.float32))
    return (guesses / np.float32(2.0 * k)).astype(np.float32)


def stream(inc: Incidence, ids, row_of, k: int, thr: np.ndarray):
    """Stream candidates (ids in arrival order; ``row_of[i]`` the row
    of candidate i) through every bucket: a bucket with fewer than k
    seeds admits a candidate whose gain on its cover reaches the
    bucket's threshold.  Returns (seeds [k], coverage, near_ties) of
    the bucket with the largest cover (the lowest such bucket);
    ``near_ties`` counts decisions within 1e-5 of a threshold."""
    nb = thr.shape[0]
    covers = [np.zeros(inc.theta, bool) for _ in range(nb)]
    counts = np.zeros(nb, np.int64)
    seeds = np.full((nb, k), -1, np.int64)
    near = 0
    for sid, r in zip(ids, row_of):
        if sid < 0:
            continue
        samp = inc.row(int(r))
        for b in range(nb):
            if counts[b] >= k:
                continue
            g = np.count_nonzero(~covers[b][samp])
            near += abs(float(g) - float(thr[b])) < 1e-5 * max(1.0,
                                                               float(thr[b]))
            if np.float32(g) >= thr[b]:
                covers[b][samp] = True
                seeds[b, counts[b]] = sid
                counts[b] += 1
    cov = np.array([int(c.sum()) for c in covers])
    best = int(np.argmax(cov))
    return seeds[best], int(cov[best]), near
