"""Max-k-cover solvers over packed incidence rows.

``greedy_maxcover`` is the jit-compatible vectorized greedy used on
"local machines" (shards) inside GreediRIS.  Four solver paths share
bit-identical semantics (seeds, rows, covered, gains — including the
lowest-index argmax tie-break), extending the streaming receiver's
``receiver="scan"|"fused"|"pipelined"`` triad to a quad:

  * ``solver="scan"`` — each of the k iterations is one full
    marginal-gain sweep + jnp.argmax (the reference/CPU path);
  * ``solver="fused"`` — each pick is one ``best_gain_index`` Pallas
    launch (gain sweep + blockwise argmax fused; the [n] gain vector
    never round-trips HBM);
  * ``solver="resident"`` — the whole greedy loop is ONE pallas_call
    (``repro.kernels.greedy_pick``): covered/picked/seeds/gains stay
    VMEM-resident across all k picks and the rows stream through a
    double-buffered VMEM tile;
  * ``solver="lazy"`` — the resident loop plus tile-level lazy greedy
    (``repro.kernels.lazy_greedy``): a [num_tiles] stale-upper-bound
    vector stays in VMEM and each pick only DMAs + re-sweeps tiles
    whose bound can still reach the running best gain (equal bounds
    still re-sweep, preserving the lowest-index tie-break bit-for-bit)
    — the TPU analogue of the paper's Algorithm 2 lazy greedy, cutting
    the resident solver's k*n*W row re-read on skewed gains.

For uniform gain profiles the memory-bound full sweeps ("resident")
win on TPU — no pointer chasing, same words touched; on skewed
profiles "lazy" skips most of the re-read while staying bit-exact.
The paper's heap-based Algorithm 2 is kept as a NumPy oracle for
equivalence tests: all paths achieve identical coverage.

``use_kernel`` is a deprecated alias: True maps to ``solver="fused"``,
False to ``solver="scan"``.
"""
from __future__ import annotations

import functools
import heapq
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset

SOLVERS = ("scan", "fused", "resident", "lazy")


class CoverSolution(NamedTuple):
    seeds: jnp.ndarray      # int32 [k] selected row indices (-1 = unused)
    rows: jnp.ndarray       # uint32 [k, W] covering rows of the seeds
    covered: jnp.ndarray    # uint32 [W] union of selected rows
    coverage: jnp.ndarray   # int32 [] total bits covered
    gains: jnp.ndarray      # int32 [k] marginal gain at each pick
    tiles_swept: jnp.ndarray  # int32 [] row tiles the picks swept


def resolve_solver(solver: str | None,
                   use_kernel: bool | None = None,
                   default: str = "scan") -> str:
    """Resolve the solver quad from the new ``solver=`` argument and
    the deprecated ``use_kernel`` bool (True -> "fused", False ->
    "scan").  ``solver`` wins when both are given — the alias is then
    inert, so the deprecation warning only fires when ``use_kernel``
    actually decides the path (keeps callers that already migrated,
    like ``im_driver``, from warning twice)."""
    if use_kernel is not None and solver is None:
        warnings.warn(
            "use_kernel is deprecated; pass solver='fused' (was "
            "use_kernel=True) or solver='scan' (was use_kernel=False)",
            DeprecationWarning, stacklevel=3)
        solver = "fused" if use_kernel else "scan"
    if solver is None:
        solver = default
    if solver not in SOLVERS:
        raise ValueError(
            f"unknown solver {solver!r}; expected one of {SOLVERS}")
    return solver


def _no_exclusions() -> jnp.ndarray:
    """The empty seed-constraint: one -1 pad slot (matches no row)."""
    return jnp.full((1,), -1, dtype=jnp.int32)


def greedy_maxcover(rows: jnp.ndarray, k: int,
                    use_kernel: bool | None = None,
                    solver: str | None = None,
                    excluded: jnp.ndarray | None = None) -> CoverSolution:
    """Vectorized greedy max-k-cover.

    rows: uint32 [n, W] packed covering sets. Returns the greedy
    (1 - 1/e)-approximate solution.  ``solver`` picks the execution
    path (see module docstring); all paths are bit-identical.

    ``excluded`` (int32 [E] row ids, -1 pads ignored) forbids rows
    from ever being selected — the per-query seed-constraint of the
    serving path (``repro.core.service``).  Excluded rows are masked
    exactly like already-picked rows on every solver, so the quad
    stays bit-identical under any exclusion set.

    Thin un-jitted shim: the solver quad (and the deprecated
    ``use_kernel`` alias, with its warning) resolves eagerly here so
    the DeprecationWarning points at the caller and fires on every
    call, not only at trace time; the jitted body is dispatched with
    the resolved solver as a static argument.
    """
    if excluded is None:
        excluded = _no_exclusions()
    return _greedy_maxcover(rows, jnp.asarray(excluded, jnp.int32), k,
                            resolve_solver(solver, use_kernel))


def greedy_maxcover_batch(rows: jnp.ndarray, excluded: jnp.ndarray,
                          k: int,
                          solver: str | None = None) -> CoverSolution:
    """Batched greedy max-k-cover: B seed-constrained queries against
    ONE shared row pool in a single vmapped solve.

    rows: uint32 [n, W] shared packed pool (``in_axes=None`` — the row
    stream is not replicated per query); excluded: int32 [B, E] per-
    query exclusion ids (-1 pads).  Returns a ``CoverSolution`` whose
    every leaf has a leading [B] axis; slice b is bit-identical to
    ``greedy_maxcover(rows, k, solver=..., excluded=excluded[b])`` for
    all four solvers.  Mixed per-query k is handled above this layer
    (``repro.core.service``) by solving at max(k) and truncating —
    greedy picks are prefix-consistent, so the truncation is exact.
    """
    return _greedy_maxcover_batch(rows, jnp.asarray(excluded, jnp.int32),
                                  k, resolve_solver(solver))


@functools.partial(jax.jit, static_argnames=("k", "solver"))
def _greedy_maxcover(rows: jnp.ndarray, excluded: jnp.ndarray, k: int,
                     solver: str) -> CoverSolution:
    return _solve_one(rows, excluded, k, solver)


@functools.partial(jax.jit, static_argnames=("k", "solver"))
def _greedy_maxcover_batch(rows: jnp.ndarray, excluded: jnp.ndarray,
                           k: int, solver: str) -> CoverSolution:
    return jax.vmap(lambda ex: _solve_one(rows, ex, k, solver))(excluded)


def full_sweep_tiles(n: int, k: int) -> int:
    """Row tiles k picks sweep when none is skipped: k times the lazy
    kernel's row tiles of an [n, W] pool (``lazy_greedy.num_row_tiles``).
    The ``tiles_swept`` of every solver but "lazy"."""
    from repro.kernels.lazy_greedy import num_row_tiles
    return k * num_row_tiles(n)


def _solve_one(rows: jnp.ndarray, excluded: jnp.ndarray, k: int,
               solver: str) -> CoverSolution:
    """One greedy solve (trace-level body — vmapped by the batch entry
    point, so everything here must be vmap-compatible)."""
    n, w = rows.shape
    full = jnp.int32(full_sweep_tiles(n, k))

    if solver == "resident":
        from repro.kernels import ops as kops
        seeds, sel_rows, covered, gains = kops.greedy_maxcover_resident(
            rows, k, excluded)
        return CoverSolution(seeds, sel_rows, covered,
                             bitset.coverage_size(covered), gains, full)

    if solver == "lazy":
        from repro.kernels import ops as kops
        # The kernel's own count of the tiles its picks re-swept.
        seeds, sel_rows, covered, gains, swept = kops.greedy_maxcover_lazy(
            rows, k, excluded)
        return CoverSolution(seeds, sel_rows, covered,
                             bitset.coverage_size(covered), gains, swept)

    if solver == "fused":
        from repro.kernels import ops as kops

        def pick(covered, picked_mask):
            return kops.best_gain_index(rows, covered, picked_mask)
    else:
        def pick(covered, picked_mask):
            g = bitset.marginal_gain(rows, covered)
            g = jnp.where(picked_mask, -1, g)
            best = jnp.argmax(g)
            return g[best], best

    def body(i, state):
        covered, seeds, sel_rows, picked_mask, gains = state
        best_gain, best = pick(covered, picked_mask)
        take = best_gain > 0
        row = jnp.where(take, rows[best], jnp.zeros((w,), bitset.WORD_DTYPE))
        covered = covered | row
        seeds = seeds.at[i].set(jnp.where(take, best.astype(jnp.int32), -1))
        sel_rows = sel_rows.at[i].set(row)
        picked_mask = picked_mask.at[best].set(take | picked_mask[best])
        gains = gains.at[i].set(jnp.where(take, best_gain, 0))
        return covered, seeds, sel_rows, picked_mask, gains

    covered = jnp.zeros((w,), dtype=bitset.WORD_DTYPE)
    seeds = jnp.full((k,), -1, dtype=jnp.int32)
    sel_rows = jnp.zeros((k, w), dtype=bitset.WORD_DTYPE)
    # Exclusions seed the picked mask: masked to gain -1 from pick 0,
    # exactly how the resident/lazy kernels mask their excl-ids block.
    valid = (excluded >= 0) & (excluded < n)
    picked = jnp.zeros((n,), dtype=bool).at[
        jnp.where(valid, excluded, 0)].max(valid)
    gains = jnp.zeros((k,), dtype=jnp.int32)
    covered, seeds, sel_rows, picked, gains = jax.lax.fori_loop(
        0, k, body, (covered, seeds, sel_rows, picked, gains))
    return CoverSolution(seeds, sel_rows, covered,
                         bitset.coverage_size(covered), gains, full)


def _popcount_words(words) -> int:
    """Word-safe host-side popcount of a packed row: each word goes
    through a Python int (``bin(...).count``), so uint64 words with the
    high bit set never detour through float the way a vectorized
    ``np.sum`` of object arrays can.  Shared by the lazy-greedy oracle
    and ``coverage_of``."""
    return sum(bin(int(x)).count("1")
               for x in np.asarray(words, dtype=np.uint64).ravel())


def lazy_greedy_maxcover_np(rows: np.ndarray, k: int) -> tuple[list, int]:
    """Paper Algorithm 2 — heap-based lazy greedy (NumPy oracle).

    Returns (seed list, total coverage).  Used in tests to certify the
    vectorized greedy matches the sequential lazy greedy coverage.
    """
    n, w = rows.shape
    covered = np.zeros(w, dtype=np.uint64)
    heap = [(-_popcount_words(rows[v]), 0, v) for v in range(n)]
    heapq.heapify(heap)                           # (-gain, stamp, v)
    seeds: list[int] = []
    stamp = 0
    while heap and len(seeds) < k:
        neg_gain, s, v = heapq.heappop(heap)
        fresh = _popcount_words(
            np.asarray(rows[v], dtype=np.uint64) & ~covered)
        if -neg_gain == fresh or (heap and fresh >= -heap[0][0]):
            if fresh == 0:
                break
            seeds.append(v)
            covered |= np.asarray(rows[v], dtype=np.uint64)
            stamp += 1
        else:
            heapq.heappush(heap, (-fresh, stamp, v))
    return seeds, _popcount_words(covered)


def coverage_of(rows: np.ndarray, seeds) -> int:
    """Coverage of an explicit seed subset (host-side check)."""
    covered = np.zeros(rows.shape[1], dtype=np.uint64)
    for s in seeds:
        if s >= 0:
            covered |= np.asarray(rows[int(s)], dtype=np.uint64)
    return _popcount_words(covered)
