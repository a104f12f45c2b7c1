"""Jitted public wrappers for the Pallas kernels.

On the CPU container the kernels execute under ``interpret=True``
(Python emulation of the kernel body — the validation mode prescribed
for this offline environment); on a real TPU backend they compile to
Mosaic.  The wrappers pick the mode from the active backend so library
code can call them unconditionally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bucket import bucket_gains_pallas
from repro.kernels.bucket_insert import (bucket_insert_chunk_pallas,
                                         bucket_insert_stream_pallas)
from repro.kernels.coverage import marginal_gain_pallas
from repro.kernels.greedy_pick import (
    greedy_maxcover_resident_batch_pallas, greedy_maxcover_resident_pallas)
from repro.kernels.lazy_greedy import greedy_maxcover_lazy_pallas
from repro.kernels.rrr_expand import (rrr_expand_step_pallas,
                                      rrr_expand_step_resident_pallas)
from repro.kernels.topk_gain import best_gain_index_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def marginal_gain(rows: jnp.ndarray, covered: jnp.ndarray) -> jnp.ndarray:
    return marginal_gain_pallas(rows, covered, interpret=_interpret())


def bucket_gains(row: jnp.ndarray, covers: jnp.ndarray) -> jnp.ndarray:
    return bucket_gains_pallas(row, covers, interpret=_interpret())


def best_gain_index(rows: jnp.ndarray, covered: jnp.ndarray,
                    picked: jnp.ndarray):
    """Fused marginal-gain + blockwise-argmax of one greedy pick (the
    ``solver="fused"`` engine): no [n] gain-vector HBM round-trip."""
    return best_gain_index_pallas(rows, covered, picked,
                                  interpret=_interpret())


def greedy_maxcover_resident(rows: jnp.ndarray, k: int,
                             excluded: jnp.ndarray | None = None):
    """Resident greedy max-k-cover (the ``solver="resident"`` engine):
    all k picks in ONE pallas_call, covered/picked/seeds/gains
    VMEM-resident for the whole loop, rows double-buffered HBM->VMEM.
    ``excluded`` (int32 [E] ids, -1 pads) forbids rows from being
    picked — the serving seed-constraint."""
    return greedy_maxcover_resident_pallas(rows, k, excluded,
                                           interpret=_interpret())


def greedy_maxcover_lazy(rows: jnp.ndarray, k: int,
                         excluded: jnp.ndarray | None = None):
    """Lazy-greedy resident max-k-cover (the ``solver="lazy"`` engine):
    one pallas_call like the resident solver, but each pick only DMAs +
    re-sweeps row tiles whose VMEM-resident stale upper bound can still
    beat the running best gain.  Returns the resident tuple plus a
    ``tiles_swept`` counter (skip ratio = swept / (k * num_tiles)).
    ``excluded`` as in :func:`greedy_maxcover_resident`."""
    return greedy_maxcover_lazy_pallas(rows, k, excluded,
                                       interpret=_interpret())


def greedy_maxcover_resident_batch(rows: jnp.ndarray, k: int,
                                   excluded: jnp.ndarray):
    """Batched-query entry point: B concurrent seed-constrained solves
    over ONE shared [n, W] row pool in a single resident launch.
    ``excluded`` is int32 [B, E] (-1 pads).  Each pick streams every
    row tile of the pool from HBM once and sweeps it against all B
    queries' VMEM-resident state (covered words, k selected rows, k seed
    and gain slots, E exclusion slots), so the pool is read k times for the batch, not
    B*k times.  Returns the resident tuple with a leading [B] axis,
    each slice bit-identical to the sequential per-query call, plus
    the kernel's count of the row tiles it DMA'd (int32 [])."""
    return greedy_maxcover_resident_batch_pallas(rows, k, excluded,
                                                 interpret=_interpret())


def greedy_maxcover_lazy_batch(rows: jnp.ndarray, k: int,
                               excluded: jnp.ndarray):
    """Batched-query lazy solve: as
    :func:`greedy_maxcover_resident_batch` but with the per-tile
    stale-bound skipping (each query keeps its own [num_tiles] bound
    vector — bounds depend on the query's exclusion set)."""
    return jax.vmap(
        lambda ex: greedy_maxcover_lazy_pallas(
            rows, k, ex, interpret=_interpret()))(excluded)


def rrr_expand_step(frontier: jnp.ndarray, visited: jnp.ndarray,
                    fwd_nbr: jnp.ndarray, gmask: jnp.ndarray,
                    block_v: int | None = None):
    """Fused packed BFS expansion step, streamed-gmask layout:
    frontier/visited words VMEM-resident, index and pre-gathered
    packed coin-mask tiles streamed double-buffered (the forward-slot
    axis tiled into the stream whenever the double buffer would
    overflow the VMEM budget), gather + AND + OR-accumulate +
    new/visited updates in ONE pallas_call per step.

    The kernel is direction-agnostic — it just gathers frontier words
    through an index table under a packed mask — so it serves both the
    RRR sampler's reverse BFS (``sampler="kernel"``: table =
    forward adjacency, coins cross-gathered via rev_slot) and the
    cascade simulator's forward diffusion (``engine="kernel"`` in
    ``core/cascade``: table = reverse adjacency, coins local)."""
    return rrr_expand_step_pallas(frontier, visited, fwd_nbr, gmask,
                                  block_v=block_v,
                                  interpret=_interpret())


def rrr_expand_step_resident(frontier: jnp.ndarray, visited: jnp.ndarray,
                             fwd_nbr: jnp.ndarray, gidx: jnp.ndarray,
                             plane: jnp.ndarray,
                             block_v: int | None = None):
    """Fused packed BFS expansion step, resident coin-plane layout
    (``gather="resident"``): the per-step packed coin-plane
    (uint32 [rows, W]) stays VMEM-resident and only int32
    ``(fwd_nbr, gidx)`` index tiles stream — BOTH gathers happen
    inside the kernel, so the XLA-side [n, d_out, W] gmask and its HBM
    round-trip never exist.  Bit-identical to
    :func:`rrr_expand_step` for ``gidx = fwd_nbr * d_pad + rev_slot``
    (invalid slots pointed at the guaranteed zero row ``rows``)."""
    return rrr_expand_step_resident_pallas(frontier, visited, fwd_nbr,
                                           gidx, plane, block_v=block_v,
                                           interpret=_interpret())


def bucket_insert_chunk(seed_ids: jnp.ndarray, rows: jnp.ndarray,
                        covers: jnp.ndarray, counts: jnp.ndarray,
                        seeds: jnp.ndarray, thresholds: jnp.ndarray):
    """Fused streaming-receiver insertion of a whole candidate chunk:
    one pallas_call with the bucket covers VMEM-resident, replacing the
    per-candidate ``bucket_gains`` launch + HBM round-trip."""
    return bucket_insert_chunk_pallas(seed_ids, rows, covers, counts,
                                      seeds, thresholds,
                                      interpret=_interpret())


def bucket_insert_stream(seed_ids: jnp.ndarray, rows: jnp.ndarray,
                         covers: jnp.ndarray, counts: jnp.ndarray,
                         seeds: jnp.ndarray, thresholds: jnp.ndarray):
    """Pipelined streaming-receiver insertion of a whole [R, C, W]
    candidate stream: one pallas_call with the bucket state
    VMEM-resident across all chunks and chunk r+1's rows DMA'd in
    (double-buffered) while chunk r inserts."""
    return bucket_insert_stream_pallas(seed_ids, rows, covers, counts,
                                       seeds, thresholds,
                                       interpret=_interpret())
