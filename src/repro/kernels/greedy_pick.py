"""Pallas TPU kernel: resident greedy max-k-cover — all k picks in
ONE pallas_call.

The sender (S3) hot path.  The scan solver launches one marginal-gain
sweep per pick, k times, round-tripping the full [n] gain vector and
the [W] covered mask through HBM between XLA ops.  Here the whole
greedy loop is resident in a single kernel:

  * the covered mask, seeds, selected rows, and per-pick gains live
    in VMEM for the entire k-pick loop — they never touch HBM until
    the final output write.  The picked mask is not stored at all:
    a row is picked iff its index appears in the resident [1, k]
    seeds block, so masking is k compares per tile instead of an
    O(n) VMEM scratch (which lane-padding would blow up to ~512
    bytes/row on TPU) — VMEM stays O(BLOCK_V*W + k*W) independent
    of n;
  * the [n, W] incidence rows stay in HBM/ANY and are streamed through
    a [2, BLOCK_V, W] VMEM scratch with double-buffered
    ``pltpu.make_async_copy`` (tile t+1 DMAs in while tile t's gains
    compute) — the same pipeline pattern as the PR 2 streaming
    receiver;
  * each pick fuses the gain sweep (the shared ``gain_core`` AND-NOT +
    popcount tile body), the blockwise argmax, the winner-row
    re-gather (one 8-row block DMA from HBM), the cover OR-update,
    and the seed/gain/row writes.

Launch/HBM-traffic model per solve (k picks over [n, W] rows):

  scan      k launches, k*(n*W + 2n + 2W) words (sweep + gain vector
            round-trip + covered round-trip per pick)
  fused     k launches, k*(n*W + 2W) words    (gain vector never
            materializes; per-block maxima only)
  resident  1 launch,   k*(n*W + W) words     (row stream re-read per
            pick + winner re-gather; covered never leaves VMEM)
  lazy      1 launch,   s*k*n*W + k*W words   (kernels/lazy_greedy.py:
            per-tile stale upper bounds skip most of the re-read on
            skewed gains; s = measured sweep fraction <= 1)

Tie-break is bit-identical to ``jnp.argmax`` over the full masked
gain vector: tiles are visited in ascending vertex order, the
within-tile pick is the lowest index holding the tile maximum, and
the cross-tile carry only replaces the incumbent on a strictly
greater gain — so ties resolve to the globally lowest index, and
all four solvers (scan / fused / resident / lazy) agree bit-for-bit
on seeds, rows, covered, and gains.  The per-tile sweep body and the
post-argmax commit are shared with the lazy kernel
(``sweep_tile_argmax`` / ``commit_pick`` below) so the bit-exactness
contract has exactly one implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gain_core

BLOCK_V = 128

# Invariants the static contract checker (repro.analysis) proves on a
# canonical fixture: the whole k-pick solve is ONE top-level launch
# (no loop wrapping it — all picks run inside the kernel), no f64 or
# float at all in the trace, no aliasing.
CONTRACT = dict(
    family="greedy_pick",
    launches=1,
    in_loop=False,
    dtypes=("bool", "int32", "uint32"),
    aliases=(),
)


def sweep_tile_argmax(tile, covered, seeds, t, block_v: int):
    """Masked gain sweep + within-tile argmax of one [BV, Wp] row tile
    — the per-pick pass body shared by the resident and lazy kernels.

    tile    uint32 [BV, Wp]  row tile (VMEM)
    covered uint32 [1, Wp]   running cover
    seeds   int32  [1, M]    masked row ids (-1 = empty slot) — the
                             resident picked set, optionally
                             concatenated with a per-query excluded-ids
                             block (seed-constraint serving)

    Returns (gain int32, index int32) of the tile's best row with
    ``jnp.argmax``'s lowest-index preference; rows whose global index
    appears in ``seeds`` are masked to gain -1 (real row indices are
    never -1, so empty slots match nothing).
    """
    g = gain_core.gain_tile_sum(tile, covered)             # [BV, 1]
    ridx_t = t * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_v, 1), 0)
    taken = jnp.any(ridx_t == seeds, axis=1, keepdims=True)  # [BV, 1]
    g = jnp.where(taken, -1, g)                            # [BV, 1]
    # argmax as max + smallest index holding it: Mosaic lowers argmax
    # only for float32, and this keeps jnp.argmax's lowest-index rule.
    best = jnp.max(g)
    lidx = jax.lax.broadcasted_iota(jnp.int32, (block_v, 1), 0)
    a = jnp.min(jnp.where(g == best, lidx, block_v))
    return best, a


def commit_pick(pick, best_gain, best_idx, rows_hbm, winner_buf, win_sem,
                covered_ref, rows_out_ref, seeds_ref, gains_ref, lane_k):
    """Fused post-argmax pick commit shared by the resident and lazy
    kernels: re-gather the winner row, then a non-positive best gain
    is rejected (seed -1, gain 0, no cover/row update — identical to
    ``jnp.argmax`` over an all-masked vector), otherwise the winner
    row ORs into the cover and the seed/gain/row outputs are written
    in place.

    The re-gather DMAs the SUBLANE-aligned [8, Wp] block holding the
    winner (a one-row HBM slice is refused by Mosaic's (8, 128)
    tiling) and selects the row inside it; ``n_pad`` is a multiple
    of SUBLANE, so the block never runs past the rows."""
    sub = winner_buf.shape[0]
    base = pl.multiple_of((best_idx // sub) * sub, sub)
    win = pltpu.make_async_copy(rows_hbm.at[pl.ds(base, sub)],
                                winner_buf, win_sem)
    win.start()
    win.wait()
    winner = winner_buf[pl.ds(best_idx - base, 1), :]      # [1, Wp]
    take = best_gain > 0
    row = jnp.where(take, winner, jnp.zeros_like(winner))
    covered_ref[...] = covered_ref[...] | row
    rows_out_ref[pl.ds(pick, 1), :] = row
    hit = lane_k == pick
    seeds_ref[...] = jnp.where(
        hit, jnp.where(take, best_idx, -1), seeds_ref[...])
    gains_ref[...] = jnp.where(
        hit, jnp.where(take, best_gain, 0), gains_ref[...])


def _kernel(rows_hbm, excl_ref, seeds_ref, rows_out_ref, covered_ref,
            gains_ref, tile_buf, winner_buf, tile_sem, win_sem, *,
            block_v: int):
    """One program: the entire k-pick greedy loop.

    rows_hbm    uint32 [n_pad, Wp]  HBM/ANY — streamed, never resident
    excl_ref    int32  [1, E]       VMEM in — excluded row ids (-1 =
                                    empty slot; seed-constraint mask
                                    of the serving path, masked
                                    exactly like the picked set)
    seeds_ref   int32  [1, k]       VMEM out (doubles as picked set)
    rows_out_ref uint32 [k, Wp]     VMEM out (selected rows)
    covered_ref uint32 [1, Wp]      VMEM out (running union)
    gains_ref   int32  [1, k]       VMEM out
    tile_buf    uint32 [2, BV, Wp]  double-buffered row-tile scratch
    winner_buf  uint32 [8, Wp]      winner-block re-gather scratch

    Zero-padded rows need no masking: their gain is 0, so with any
    positive gain left they lose the argmax, at equal gain 0 the
    lowest-index tie-break prefers the (lower) real indices, and when
    everything real is masked a winning pad row's gain 0 is rejected
    (take = gain > 0) exactly like the scan path's all-masked
    argmax — identical outputs in every case.
    """
    n_pad = rows_hbm.shape[0]
    k = seeds_ref.shape[1]
    num_tiles = n_pad // block_v

    covered_ref[...] = jnp.zeros_like(covered_ref)
    seeds_ref[...] = jnp.full_like(seeds_ref, -1)
    gains_ref[...] = jnp.zeros_like(gains_ref)
    rows_out_ref[...] = jnp.zeros_like(rows_out_ref)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def tile_dma(slot, t):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(t * block_v, block_v)],
            tile_buf.at[slot], tile_sem.at[slot])

    def pick_body(pick, _):
        # --- pass 1: streamed gain sweep + blockwise argmax ---------
        tile_dma(0, 0).start()

        def tile_body(t, best):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < num_tiles)
            def _prefetch():
                tile_dma(jax.lax.rem(t + 1, 2), t + 1).start()

            tile_dma(slot, t).wait()
            mask_ids = jnp.concatenate(
                [seeds_ref[...], excl_ref[...]], axis=1)
            ga, a = sweep_tile_argmax(tile_buf[slot], covered_ref[...],
                                      mask_ids, t, block_v)
            bg, bi = best
            better = ga > bg                 # strict: keep lowest tile
            return (jnp.where(better, ga, bg),
                    jnp.where(better, t * block_v + a, bi))

        best_gain, best_idx = jax.lax.fori_loop(
            0, num_tiles, tile_body, (jnp.int32(-1), jnp.int32(0)))

        # --- winner re-gather + cover OR, seed/gain/row writes ------
        commit_pick(pick, best_gain, best_idx, rows_hbm, winner_buf,
                    win_sem, covered_ref, rows_out_ref, seeds_ref,
                    gains_ref, lane_k)
        return 0

    jax.lax.fori_loop(0, k, pick_body, 0)


@functools.partial(jax.jit, static_argnames=("k", "block_v", "interpret"))
def greedy_maxcover_resident_pallas(rows: jnp.ndarray, k: int,
                                    excluded: jnp.ndarray | None = None,
                                    block_v: int | None = None,
                                    interpret: bool = False):
    """Resident greedy max-k-cover: rows uint32 [n, W] ->
    (seeds int32 [k], sel_rows uint32 [k, W], covered uint32 [W],
    gains int32 [k]) in a single pallas_call.

    Bit-identical to the scan solver (``maxcover.greedy_maxcover`` with
    ``solver="scan"``) including the lowest-index argmax tie-break and
    the exhausted-gain behaviour (best gain <= 0 -> seed -1, gain 0,
    no cover/picked update, identical to argmax over an all-masked
    vector).  Zero row/word padding is exact: padded rows have gain 0
    and are never taken (see ``_kernel``), padded words contribute
    popcount 0.

    ``excluded`` (int32 [E], -1 = empty slot) forbids row ids from ever
    being picked — the per-query seed-constraint of the serving path
    (``repro.core.service``).  Excluded ids are masked to gain -1 in
    every sweep, exactly like already-picked rows, so the outputs match
    the scan solver with the same ids pre-set in its picked mask
    bit-for-bit.  The [1, E] block rides in VMEM next to the seeds —
    per-query state stays O(k + E + W), independent of n.
    """
    n, w = rows.shape
    if excluded is None:
        excluded = jnp.full((1,), -1, jnp.int32)
    excl = jnp.asarray(excluded, jnp.int32).reshape(1, -1)
    if block_v is None:   # tuned table (falls back to BLOCK_V)
        from repro.kernels import vmem_budget
        block_v = vmem_budget.auto_block_v("greedy_pick", BLOCK_V)
    bv = gain_core.effective_block(
        n, block_v, gain_core.SUBLANE)
    bv = gain_core.padded_size(bv, gain_core.SUBLANE)
    n_pad = gain_core.padded_size(n, bv)
    wp = gain_core.padded_size(w, gain_core.LANE)
    if n_pad != n or wp != w:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, wp - w)))
    seeds, sel_rows, covered, gains = pl.pallas_call(
        functools.partial(_kernel, block_v=bv),
        name="greedy_pick_resident",
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((k, wp), rows.dtype),
            jax.ShapeDtypeStruct((1, wp), rows.dtype),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bv, wp), rows.dtype),   # row-tile double buf
            pltpu.VMEM((gain_core.SUBLANE, wp), rows.dtype),  # winner block
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(rows, excl)
    return seeds[0], sel_rows[:, :w], covered[0, :w], gains[0]
