"""Pallas TPU kernel: bit-exact lazy-greedy resident max-k-cover —
all k picks in ONE pallas_call, with per-tile stale-bound skipping.

The resident sender (``kernels/greedy_pick.py``) re-reads the entire
[n, W] row stream on every one of the k picks — k*n*W words, the
dominant HBM-traffic term in its launch model.  The paper's
Algorithm 2 lazy greedy avoids almost all re-evaluations once gains
are skewed: a candidate's stale gain is an upper bound on its fresh
gain (marginal gains are monotone non-increasing under
submodularity), so anything whose bound cannot beat the running best
need not be re-evaluated.  This kernel is the TPU analogue at tile
granularity:

  * a [num_tiles] stale-upper-bound vector lives in SMEM for the
    whole solve; entry t holds the masked gain maximum of tile t as
    of the last time the tile was swept (init: +inf, so pick 0 sweeps
    everything);
  * on each pick, tiles are visited in ascending order and a tile is
    DMA'd + re-swept only when its stale bound is >= the running best
    gain; a swept tile refreshes its bound to the fresh masked max
    (valid for all later picks — the cover only grows and the picked
    set only grows, so tile maxima only decrease);
  * everything else — covered/seeds/rows/gains VMEM-resident, the
    double-buffered ``make_async_copy`` row-tile stream, the winner
    single-row re-gather — is the ``greedy_pick`` resident pattern;
    the per-tile sweep and the pick commit are literally
    ``greedy_pick.sweep_tile_argmax`` / ``greedy_pick.commit_pick``,
    so the bit-exactness contract has one implementation.

The skip decision reads (and the sweep writes) the bound vector at a
dynamic tile index — ``ub_ref[t]`` with a traced ``t``.  Mosaic
refuses scalar stores to VMEM, so the bounds live in SMEM like
``best_ref``/``cnt_ref`` (an int32 [num_tiles] vector: 8 KiB at
n=2^18 with 128-row tiles).

Tie-break stays bit-identical to ``jnp.argmax`` over the full masked
gain vector.  The skip rule is *strict less-than*: a tile whose bound
EQUALS the running best is still re-swept.  Equality matters for the
lowest-index convention only through the cross-tile carry, which (as
in ``greedy_pick``) replaces the incumbent on strictly greater gain
only — so a re-swept equal-bound tile can never steal a tie from a
lower-index incumbent, and a skipped tile (bound < best, hence fresh
max < best after the strict compare too) could never have won.
Sweeping at equality keeps the rule conservative and the outputs
bit-for-bit identical to the scan/fused/resident solvers in every
case, including exhausted gains and padded rows.

Prefetch note: to keep tile t+1's DMA overlapped with tile t's gain
sweep (the double-buffer pattern), the skip decision for tile t+1 is
taken *before* tile t's sweep result merges into the running best.
The decision is therefore taken against a best that is <= the final
value — a conservative superset of the exactly-lazy sweep set — so
bit-exactness is unaffected and no needed tile is ever skipped; a
tile skipped under the lagged best would also be skipped under the
final best of every earlier tile.  (When tile t itself is skipped the
decision for t+1 is exact.)

The kernel also counts the tiles it actually swept (``tiles_swept``,
summed over all k picks) so benchmarks can report the measured skip
ratio tiles_swept / (k * num_tiles) — the fraction of the resident
kernel's k*n*W re-read the lazy bound actually pays.

Launch/HBM-traffic model per solve (k picks over [n, W] rows,
s = measured skip... sweep fraction in [1/(k*num_tiles), 1]):

  resident  1 launch, k*(n*W + W) words
  lazy      1 launch, s*k*n*W + k*W words  (only swept tiles stream;
            s -> n_tiles^-1 per pick on fully skewed gains, 1 on
            uniform gains)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gain_core, greedy_pick

BLOCK_V = 128

# Static contract (proved by repro.analysis on a canonical fixture):
# one top-level launch for all k picks, stale-bound skipping included;
# integer/bool trace only; no aliasing.
CONTRACT = dict(
    family="lazy_greedy",
    launches=1,
    in_loop=False,
    dtypes=("bool", "int32", "uint32"),
    aliases=(),
)

# Upper-bound initializer: larger than any achievable gain (< 2^31).
_UB_INIT = jnp.iinfo(jnp.int32).max


def num_row_tiles(n: int, block_v: int | None = None) -> int:
    """Number of row tiles the lazy kernel sweeps per full pass — the
    denominator of the skip ratio (total sweeps possible = k * tiles).
    ``block_v=None`` resolves exactly like the kernel wrapper (tuned
    table, then BLOCK_V) so external ratio math stays consistent."""
    if block_v is None:
        from repro.kernels import vmem_budget
        block_v = vmem_budget.auto_block_v("lazy_greedy", BLOCK_V)
    bv = gain_core.effective_block(n, block_v, gain_core.SUBLANE)
    bv = gain_core.padded_size(bv, gain_core.SUBLANE)
    return gain_core.padded_size(n, bv) // bv


def _kernel(rows_hbm, excl_ref, seeds_ref, rows_out_ref, covered_ref,
            gains_ref, swept_ref, ub_ref, best_ref, cnt_ref, tile_buf,
            winner_buf, tile_sem, win_sem, *, block_v: int):
    """One program: the entire k-pick lazy-greedy loop.

    rows_hbm    uint32 [n_pad, Wp]  HBM/ANY — streamed, never resident
    excl_ref    int32  [1, E]       VMEM in — excluded row ids (-1 =
                                    empty; the serving seed-constraint,
                                    masked like the picked set; fixed
                                    for the whole solve, so the stale
                                    bounds stay valid upper bounds)
    seeds_ref   int32  [1, k]       VMEM out (doubles as picked set)
    rows_out_ref uint32 [k, Wp]     VMEM out (selected rows)
    covered_ref uint32 [1, Wp]      VMEM out (running union)
    gains_ref   int32  [1, k]       VMEM out
    swept_ref   int32  [1, 1]       VMEM out (tiles swept, all picks)
    ub_ref      int32  [T]          SMEM scratch — stale per-tile
                                    upper bounds (T tiles)
    best_ref    int32  [1, 2]       SMEM scratch — running (gain, idx)
    cnt_ref     int32  [1, 1]       SMEM scratch — tiles-swept counter
    tile_buf    uint32 [2, BV, Wp]  double-buffered row-tile scratch
    winner_buf  uint32 [8, Wp]      winner-block re-gather scratch

    The running best lives in SMEM (not the fori carry) because the
    sweep happens under ``pl.when`` — a skipped tile must leave it
    untouched without a select over a computed value.
    """
    n_pad = rows_hbm.shape[0]
    k = seeds_ref.shape[1]
    num_tiles = n_pad // block_v

    covered_ref[...] = jnp.zeros_like(covered_ref)
    seeds_ref[...] = jnp.full_like(seeds_ref, -1)
    gains_ref[...] = jnp.zeros_like(gains_ref)
    rows_out_ref[...] = jnp.zeros_like(rows_out_ref)

    def init_bound(t, _):
        ub_ref[t] = jnp.int32(_UB_INIT)
        return 0

    jax.lax.fori_loop(0, num_tiles, init_bound, 0)
    cnt_ref[0, 0] = jnp.int32(0)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def tile_dma(slot, t):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(t * block_v, block_v)],
            tile_buf.at[slot], tile_sem.at[slot])

    def pick_body(pick, _):
        best_ref[0, 0] = jnp.int32(-1)   # running best gain
        best_ref[0, 1] = jnp.int32(0)    # running best row index

        # Warm-up: decide tile 0 against the -1 init best (stale
        # bounds are masked maxima >= -1, so tile 0 always sweeps —
        # the same "first unskipped tile seeds the carry" behaviour
        # as the full sweep).
        d0 = ub_ref[0] >= best_ref[0, 0]

        @pl.when(d0)
        def _warmup():
            tile_dma(0, 0).start()

        def tile_body(t, carry):
            slot, d_cur = carry
            # Lazy skip decision for tile t+1, taken against the best
            # BEFORE tile t's sweep merges (see module docstring): a
            # conservative superset of the exact sweep set, so the
            # t+1 DMA overlaps tile t's gain sweep.
            bg_pre = best_ref[0, 0]
            t_nxt = jnp.minimum(t + 1, num_tiles - 1)
            d_next = jnp.logical_and(t + 1 < num_tiles,
                                     ub_ref[t_nxt] >= bg_pre)
            nslot = jnp.where(d_cur, 1 - slot, slot)

            @pl.when(d_next)
            def _prefetch():
                tile_dma(nslot, t + 1).start()

            @pl.when(d_cur)
            def _sweep():
                tile_dma(slot, t).wait()
                mask_ids = jnp.concatenate(
                    [seeds_ref[...], excl_ref[...]], axis=1)
                ga, a = greedy_pick.sweep_tile_argmax(
                    tile_buf[slot], covered_ref[...], mask_ids,
                    t, block_v)
                # Refresh the stale bound: the fresh masked max upper-
                # bounds every later pick's masked max of this tile.
                ub_ref[t] = ga
                bg = best_ref[0, 0]
                better = ga > bg             # strict: keep lowest tile
                best_ref[0, 0] = jnp.where(better, ga, bg)
                best_ref[0, 1] = jnp.where(
                    better, t * block_v + a, best_ref[0, 1])
                cnt_ref[0, 0] = cnt_ref[0, 0] + 1

            return (nslot, d_next)

        jax.lax.fori_loop(0, num_tiles, tile_body, (jnp.int32(0), d0))
        best_gain = best_ref[0, 0]
        best_idx = best_ref[0, 1]

        # --- winner re-gather + cover OR, seed/gain/row writes ------
        greedy_pick.commit_pick(pick, best_gain, best_idx, rows_hbm,
                                winner_buf, win_sem, covered_ref,
                                rows_out_ref, seeds_ref, gains_ref,
                                lane_k)
        return 0

    jax.lax.fori_loop(0, k, pick_body, 0)
    swept_ref[...] = jnp.zeros_like(swept_ref) + cnt_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("k", "block_v", "interpret"))
def greedy_maxcover_lazy_pallas(rows: jnp.ndarray, k: int,
                                excluded: jnp.ndarray | None = None,
                                block_v: int | None = None,
                                interpret: bool = False):
    """Lazy-greedy resident max-k-cover: rows uint32 [n, W] ->
    (seeds int32 [k], sel_rows uint32 [k, W], covered uint32 [W],
    gains int32 [k], tiles_swept int32 []) in a single pallas_call.

    Bit-identical to the scan/fused/resident solvers
    (``maxcover.greedy_maxcover``) in seeds, rows, covered, and gains —
    including the lowest-index argmax tie-break (equal stale bounds
    still re-sweep; see module docstring) and the exhausted-gain
    behaviour (best gain <= 0 -> seed -1, gain 0, no cover update).
    Zero row/word padding is exact exactly as in ``greedy_pick``.

    ``excluded`` (int32 [E], -1 = empty slot) forbids row ids from
    ever being picked — the serving seed-constraint, masked like the
    picked set (see ``greedy_pick``).  The exclusion set is fixed for
    the whole solve, so swept-tile maxima remain monotone
    non-increasing and the stale bounds stay valid.

    ``tiles_swept`` counts the row tiles actually DMA'd + re-swept
    across all k picks; the skip ratio is
    ``tiles_swept / (k * num_row_tiles(n, block_v))``.
    """
    n, w = rows.shape
    if excluded is None:
        excluded = jnp.full((1,), -1, jnp.int32)
    excl = jnp.asarray(excluded, jnp.int32).reshape(1, -1)
    if block_v is None:   # tuned table (falls back to BLOCK_V)
        from repro.kernels import vmem_budget
        block_v = vmem_budget.auto_block_v("lazy_greedy", BLOCK_V)
    bv = gain_core.effective_block(n, block_v, gain_core.SUBLANE)
    bv = gain_core.padded_size(bv, gain_core.SUBLANE)
    n_pad = gain_core.padded_size(n, bv)
    wp = gain_core.padded_size(w, gain_core.LANE)
    if n_pad != n or wp != w:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, wp - w)))
    num_tiles = n_pad // bv
    seeds, sel_rows, covered, gains, swept = pl.pallas_call(
        functools.partial(_kernel, block_v=bv),
        name="lazy_greedy",
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
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
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((num_tiles,), jnp.int32),   # stale upper bounds
            pltpu.SMEM((1, 2), jnp.int32),         # running (gain, idx)
            pltpu.SMEM((1, 1), jnp.int32),         # tiles-swept counter
            pltpu.VMEM((2, bv, wp), rows.dtype),   # row-tile double buf
            pltpu.VMEM((gain_core.SUBLANE, wp), rows.dtype),  # winner block
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(rows, excl)
    return (seeds[0], sel_rows[:, :w], covered[0, :w], gains[0],
            swept[0, 0])
