"""Pallas TPU kernel: resident greedy max-k-cover — all k picks of a
batch of B seed-constrained queries in ONE pallas_call, the shared
row pool streamed once per pick for the whole batch.

The sender (S3) and service hot path.  The scan solver launches one
marginal-gain sweep per pick, k times, round-tripping the full [n]
gain vector and the [W] covered mask through HBM between XLA ops.
Here the whole greedy loop is resident in a single kernel:

  * each query's covered mask, seeds, selected rows, and per-pick
    gains live in VMEM for the entire k-pick loop — they never touch
    HBM until the final output write.  The picked mask is not stored
    at all: a row is picked iff its index appears in the query's
    resident [1, k] seeds row, so masking is k compares per tile
    instead of an O(n) VMEM scratch (which lane-padding would blow up
    to ~512 bytes/row on TPU) — VMEM stays O(BLOCK_V*W + B*k*W)
    independent of n;
  * the [n, W] incidence rows stay in HBM/ANY and are streamed through
    a [2, BLOCK_V, W] VMEM scratch with double-buffered
    ``pltpu.make_async_copy`` (tile t+1 DMAs in while tile t's gains
    compute) — the same pipeline pattern as the PR 2 streaming
    receiver;
  * every tile that lands is swept against all B queries' covers and
    masked ids (the queries differ only in that small state), so the
    pool is read k times per batch, not B*k times;
  * each pick fuses the gain sweep (the shared ``gain_core`` AND-NOT +
    popcount tile body), the blockwise argmax, the winner-row
    re-gather (one 8-row block DMA from HBM per query, the B DMAs in
    flight together), the cover OR-update, and the seed/gain/row
    writes.

A single solve is the batch of one (``greedy_maxcover_resident_pallas``).

Launch/HBM-traffic model per solve (k picks over [n, W] rows, words;
the winner re-gather reads an 8-row block):

  scan      k launches, k*(n*W + 2n + 2W) words (sweep + gain vector
            round-trip + covered round-trip per pick)
  fused     k launches, k*(n*W + 2W) words    (gain vector never
            materializes; per-block maxima only)
  resident  1 launch,   k*(n*W + 8W) words    (row stream re-read per
            pick + winner re-gather; covered never leaves VMEM)
  batched   1 launch,   k*n*W + B*k*8*W words (B queries: the row
            stream re-read once per pick for the whole batch, one
            winner block per query and pick)
  lazy      1 launch,   s*k*n*W + k*8*W words (kernels/lazy_greedy.py:
            per-tile stale upper bounds skip most of the re-read on
            skewed gains; s = measured sweep fraction <= 1)

Tie-break is bit-identical to ``jnp.argmax`` over the full masked
gain vector: tiles are visited in ascending vertex order, the
within-tile pick is the lowest index holding the tile maximum, and
the cross-tile carry only replaces the incumbent on a strictly
greater gain — so ties resolve to the globally lowest index, and
all four solvers (scan / fused / resident / lazy) agree bit-for-bit
on seeds, rows, covered, and gains.  The within-tile argmax and the
post-argmax commit are shared with the lazy kernel (``tile_argmax``
/ ``commit_row`` below, reached there through ``sweep_tile_argmax``
/ ``commit_pick``) so the bit-exactness contract has exactly one
implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gain_core, vmem_budget

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


def tile_argmax(g, seeds, t, block_v: int):
    """Mask + within-tile argmax of one row tile's [BV, 1] gains.

    seeds   int32  [1, M]    masked row ids (-1 = empty slot) — the
                             resident picked set, optionally
                             concatenated with a per-query excluded-ids
                             block (seed-constraint serving)

    Returns (gain int32, index int32) of the tile's best row with
    ``jnp.argmax``'s lowest-index preference; rows whose global index
    appears in ``seeds`` are masked to gain -1 (real row indices are
    never -1, so empty slots match nothing).
    """
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


def sweep_tile_argmax(tile, covered, seeds, t, block_v: int):
    """Masked gain sweep + within-tile argmax of one [BV, Wp] row tile
    — the per-pick pass body of the lazy kernel.

    tile    uint32 [BV, Wp]  row tile (VMEM)
    covered uint32 [1, Wp]   running cover
    seeds   int32  [1, M]    masked row ids (see ``tile_argmax``)
    """
    g = gain_core.gain_tile_sum(tile, covered)             # [BV, 1]
    return tile_argmax(g, seeds, t, block_v)


def commit_row(pick, best_gain, best_idx, winner, covered_ref,
               rows_out_ref, out_row, seeds_ref, gains_ref, lane_k):
    """Commit one query's pick from its re-gathered winner row
    (uint32 [1, Wp]) into its state, row ``out_row`` of the selected
    rows: a non-positive best gain is rejected (seed -1,
    gain 0, no cover/row update — identical to ``jnp.argmax`` over an
    all-masked vector), otherwise the winner row ORs into the cover
    and the seed/gain/row outputs are written in place."""
    take = best_gain > 0
    row = jnp.where(take, winner, jnp.zeros_like(winner))
    covered_ref[...] = covered_ref[...] | row
    rows_out_ref[pl.ds(out_row, 1), :] = row
    hit = lane_k == pick
    seeds_ref[...] = jnp.where(
        hit, jnp.where(take, best_idx, -1), seeds_ref[...])
    gains_ref[...] = jnp.where(
        hit, jnp.where(take, best_gain, 0), gains_ref[...])


def _winner_block(best_idx, sub: int):
    """First row of the SUBLANE-aligned [8, Wp] block holding the
    winner (a one-row HBM slice is refused by Mosaic's (8, 128)
    tiling); ``n_pad`` is a multiple of SUBLANE, so the block never
    runs past the rows."""
    return pl.multiple_of((best_idx // sub) * sub, sub)


def commit_pick(pick, best_gain, best_idx, rows_hbm, winner_buf, win_sem,
                covered_ref, rows_out_ref, seeds_ref, gains_ref, lane_k):
    """Fused post-argmax pick commit of the lazy kernel: re-gather the
    winner's [8, Wp] block, select the row inside it, then
    ``commit_row``."""
    sub = winner_buf.shape[0]
    base = _winner_block(best_idx, sub)
    win = pltpu.make_async_copy(rows_hbm.at[pl.ds(base, sub)],
                                winner_buf, win_sem)
    win.start()
    win.wait()
    commit_row(pick, best_gain, best_idx,
               winner_buf[pl.ds(best_idx - base, 1), :], covered_ref,
               rows_out_ref, pick, seeds_ref, gains_ref, lane_k)


def _kernel(rows_hbm, excl_ref, seeds_ref, rows_out_ref, covered_ref,
            gains_ref, dmas_ref, cnt_ref, tile_buf, winner_buf, tile_sem,
            win_sem, *, block_v: int):
    """One program: the entire k-pick greedy loop of B queries.

    rows_hbm    uint32 [n_pad, Wp]  HBM/ANY — streamed, never resident
    excl_ref    int32  [B, E]       VMEM in — excluded row ids (-1 =
                                    empty slot; seed-constraint mask
                                    of the serving path, masked
                                    exactly like the picked set)
    seeds_ref   int32  [B, k]       VMEM out (doubles as picked sets)
    rows_out_ref uint32 [B*k, Wp]   VMEM out (selected rows, query
                                    b's in rows b*k .. b*k + k-1)
    covered_ref uint32 [B, Wp]      VMEM out (running unions)
    gains_ref   int32  [B, k]       VMEM out
    dmas_ref    int32  [1, 1]       VMEM out (row-tile DMAs issued)
    cnt_ref     int32  [1]          SMEM scratch — row-tile DMA counter
    tile_buf    uint32 [2, BV, Wp]  double-buffered row-tile scratch
    winner_buf  uint32 [B, 8, Wp]   winner-block re-gather scratch

    Each pick is one pass over the row tiles; every tile is loaded
    from VMEM once and swept against all B covers (their complements
    hoisted out of the pass) and masked ids, each query carrying its
    own best (gain, index) with the strict-greater cross-tile rule.

    Zero-padded rows need no masking: their gain is 0, so with any
    positive gain left they lose the argmax, at equal gain 0 the
    lowest-index tie-break prefers the (lower) real indices, and when
    everything real is masked a winning pad row's gain 0 is rejected
    (take = gain > 0) exactly like the scan path's all-masked
    argmax — identical outputs in every case.
    """
    n_pad = rows_hbm.shape[0]
    nq, k = seeds_ref.shape
    sub = winner_buf.shape[1]
    num_tiles = n_pad // block_v

    covered_ref[...] = jnp.zeros_like(covered_ref)
    seeds_ref[...] = jnp.full_like(seeds_ref, -1)
    gains_ref[...] = jnp.zeros_like(gains_ref)
    rows_out_ref[...] = jnp.zeros_like(rows_out_ref)
    cnt_ref[0] = jnp.int32(0)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def tile_dma(slot, t):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(t * block_v, block_v)],
            tile_buf.at[slot], tile_sem.at[slot])

    def start_tile(slot, t):
        tile_dma(slot, t).start()
        cnt_ref[0] = cnt_ref[0] + 1

    def win_dma(q, base):
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(base, sub)],
                                     winner_buf.at[q], win_sem.at[q])

    def pick_body(pick, _):
        # Per-pick query state, fixed for the whole pass.
        uncovered = ~covered_ref[...]                      # [B, Wp]
        mask_ids = jnp.concatenate([seeds_ref[...], excl_ref[...]],
                                   axis=1)                 # [B, k + E]

        # --- pass: streamed gain sweep + blockwise argmax, B queries
        start_tile(0, 0)

        def tile_body(t, best):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < num_tiles)
            def _prefetch():
                start_tile(jax.lax.rem(t + 1, 2), t + 1)

            tile_dma(slot, t).wait()
            tile = tile_buf[slot]
            out = []
            for q in range(nq):
                g = gain_core.uncovered_tile_sum(tile, uncovered[q:q + 1])
                ga, a = tile_argmax(g, mask_ids[q:q + 1], t, block_v)
                bg, bi = best[q]
                better = ga > bg             # strict: keep lowest tile
                out.append((jnp.where(better, ga, bg),
                            jnp.where(better, t * block_v + a, bi)))
            return tuple(out)

        best = jax.lax.fori_loop(
            0, num_tiles, tile_body,
            tuple((jnp.int32(-1), jnp.int32(0)) for _ in range(nq)))

        # --- winner re-gathers (all in flight together), commits ----
        bases = [_winner_block(bi, sub) for _, bi in best]
        for q in range(nq):
            win_dma(q, bases[q]).start()
        for q in range(nq):
            win_dma(q, bases[q]).wait()
        for q, (bg, bi) in enumerate(best):
            commit_row(pick, bg, bi,
                       winner_buf[q, pl.ds(bi - bases[q], 1), :],
                       covered_ref.at[pl.ds(q, 1)], rows_out_ref,
                       q * k + pick, seeds_ref.at[pl.ds(q, 1)],
                       gains_ref.at[pl.ds(q, 1)], lane_k)
        return 0

    jax.lax.fori_loop(0, k, pick_body, 0)
    dmas_ref[...] = jnp.zeros_like(dmas_ref) + cnt_ref[0]


def _block_geometry(n: int, w: int, block_v: int | None):
    """(row tile, padded rows, padded words) of an [n, w] pool."""
    if block_v is None:   # tuned table (falls back to BLOCK_V)
        block_v = vmem_budget.auto_block_v("greedy_pick", BLOCK_V)
    bv = gain_core.effective_block(n, block_v, gain_core.SUBLANE)
    bv = gain_core.padded_size(bv, gain_core.SUBLANE)
    return (bv, gain_core.padded_size(n, bv),
            gain_core.padded_size(w, gain_core.LANE))


@functools.partial(jax.jit, static_argnames=("k", "block_v", "interpret"))
def greedy_maxcover_resident_batch_pallas(rows: jnp.ndarray, k: int,
                                          excluded: jnp.ndarray,
                                          block_v: int | None = None,
                                          interpret: bool = False):
    """Resident greedy max-k-cover of B queries over one shared pool:
    rows uint32 [n, W], excluded int32 [B, E] (-1 = empty slot) ->
    (seeds int32 [B, k], sel_rows uint32 [B, k, W], covered uint32
    [B, W], gains int32 [B, k], tile_dmas int32 []) in one
    pallas_call (see below for a batch past the VMEM budget).

    Slice b is bit-identical to the scan solver
    (``maxcover.greedy_maxcover`` with ``solver="scan"`` and
    ``excluded=excluded[b]``) including the lowest-index argmax
    tie-break and the exhausted-gain behaviour (best gain <= 0 ->
    seed -1, gain 0, no cover/picked update, identical to argmax over
    an all-masked vector).  Zero row/word padding is exact: padded
    rows have gain 0 and are never taken (see ``_kernel``), padded
    words contribute popcount 0.

    Excluded ids are masked to gain -1 in every sweep, exactly like
    already-picked rows — the per-query seed-constraint of the
    serving path (``repro.core.service``).  Per-query state rides in
    VMEM and stays O(k*W + E), independent of n.

    ``tile_dmas`` is the kernel's own count of the row tiles it DMA'd
    from the pool: k times its row tiles whatever B is, as
    long as the B queries' state fits one launch's VMEM budget
    (``vmem_budget.sender_batch_queries``); a larger batch is split
    into as few launches as fit, each streaming the pool.
    """
    n, w = rows.shape
    excl = jnp.asarray(excluded, jnp.int32)
    bv, n_pad, wp = _block_geometry(n, w, block_v)
    if n_pad != n or wp != w:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, wp - w)))
    group = vmem_budget.sender_batch_queries(k, excl.shape[1], wp, bv)
    outs = [_launch(rows, excl[i:i + group], k, bv, interpret)
            for i in range(0, excl.shape[0], group)]
    seeds, sel_rows, covered, gains, dmas = (
        jnp.concatenate(parts) for parts in zip(*outs))
    return (seeds, sel_rows[:, :, :w], covered[:, :w], gains,
            jnp.sum(dmas))


def _launch(rows, excl, k: int, bv: int, interpret: bool):
    """One launch of the kernel over padded rows for the queries of
    ``excl`` (int32 [B, E]); tile_dmas as int32 [1]."""
    nq = excl.shape[0]
    wp = rows.shape[1]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    seeds, sel_rows, covered, gains, dmas = pl.pallas_call(
        functools.partial(_kernel, block_v=bv),
        name="greedy_pick_resident",
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), vmem],
        out_specs=[vmem] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
            jax.ShapeDtypeStruct((nq * k, wp), rows.dtype),
            jax.ShapeDtypeStruct((nq, wp), rows.dtype),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.int32),           # row-tile DMA counter
            pltpu.VMEM((2, bv, wp), rows.dtype),   # row-tile double buf
            pltpu.VMEM((nq, gain_core.SUBLANE, wp), rows.dtype),  # winners
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((nq,)),
        ],
        interpret=interpret,
    )(rows, excl)
    return seeds, sel_rows.reshape(nq, k, wp), covered, gains, dmas[0]


def greedy_maxcover_resident_pallas(rows: jnp.ndarray, k: int,
                                    excluded: jnp.ndarray | None = None,
                                    block_v: int | None = None,
                                    interpret: bool = False):
    """Resident greedy max-k-cover of one query: rows uint32 [n, W] ->
    (seeds int32 [k], sel_rows uint32 [k, W], covered uint32 [W],
    gains int32 [k]) — the batch of one of
    :func:`greedy_maxcover_resident_batch_pallas`.

    ``excluded`` (int32 [E], -1 = empty slot) forbids row ids from ever
    being picked; the outputs match the scan solver with the same ids
    pre-set in its picked mask bit-for-bit.
    """
    if excluded is None:
        excluded = jnp.full((1,), -1, jnp.int32)
    excl = jnp.asarray(excluded, jnp.int32).reshape(1, -1)
    seeds, sel_rows, covered, gains, _ = \
        greedy_maxcover_resident_batch_pallas(rows, k, excl,
                                              block_v=block_v,
                                              interpret=interpret)
    return seeds[0], sel_rows[0], covered[0], gains[0]
