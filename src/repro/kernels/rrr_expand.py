"""Pallas TPU kernels: fused packed RRR BFS expansion — one launch per
BFS step, with the gathers inside the kernel.

The sampler (S1) hot path.  The packed JAX expansion
(``repro.core.rrr._expand_packed``) materializes three [n, d_out, W]
word tensors per BFS step — the gathered frontier rows, their AND with
the gathered coin masks, and the pre-reduction contributions — plus
the hit/new/visited elementwise passes, each round-tripping HBM.  Here
one BFS step is ONE pallas_call, in one of two layouts sharing a tile
body (gather + AND + OR-accumulate + ``new = hit & ~visited`` /
``visited |= new``, outputs written tile-by-tile):

  * ``rrr_expand_step_resident_pallas`` — the per-step packed
    coin-plane (uint32 [rows, W]: the once-per-step coins in chunk
    layout, ``rows = n * d_pad`` — orders of magnitude smaller than
    the [n, d_out, W] gmask it replaces) stays VMEM-resident next to
    the frontier/visited words, and the streamed tiles are only the
    int32 ``(fwd_nbr, gidx)`` index pairs (``gidx = fwd_nbr * d_pad +
    rev_slot`` flattened into the plane).  BOTH gathers — frontier
    rows at ``fwd_nbr``, coin words at ``gidx`` — happen inside the
    kernel, so the XLA-side [n, d_out, W] gmask gather and its HBM
    write+read round-trip disappear entirely (pinned by a jaxpr
    assertion in the tests: no gmask-shaped intermediate).
  * ``rrr_expand_step_pallas`` (streamed) — the fallback when the
    coin-plane itself exceeds the VMEM budget: XLA pre-gathers the
    packed coin masks to forward order and the kernel streams
    ``(fwd_nbr, gmask)`` tile pairs HBM→VMEM through double-buffered
    ``pltpu.make_async_copy`` pairs (tile t+1 DMAs in while tile t
    computes) — the same pipeline pattern as the resident sender
    (``greedy_pick.py``) and the streaming receiver.

Both layouts tile the stream's **forward-slot (d_out) axis**: the
stream is laid out ``[num_d_tiles * n_pad, ...]`` with tile
``(t, d_i)`` at row offset ``d_i * n_pad + t * BV``, and the kernel
OR-accumulates partial hits into a [BV, Wp] VMEM scratch, emitting the
new/visited updates on the last d-tile.  The double-buffer scratch is
therefore O(BV · d_tile · W) instead of O(BV · d_out · W) — heavy-hub
graphs no longer overflow the ~14 MiB budget; the tile size comes from
``kernels.vmem_budget.sampler_d_tile`` (tuned table first, analytic
solve as fallback) unless pinned by the caller.  OR-accumulation is
order-free, so splitting a vertex row across stream tiles is bit-exact.

The kernel is direction-agnostic — it gathers frontier words through
an index table under a packed mask — so both layouts serve the RRR
sampler's reverse BFS (``sampler="kernel"``) and the cascade
simulator's forward diffusion (``engine="kernel"``) unchanged; the
``gather="resident"|"streamed"|"auto"`` knob picking between them
lives in ``kernels.vmem_budget.resolve_gather``.

Mosaic caveat (the ROADMAP TPU timing item): the in-kernel gathers
read VMEM-resident rows at traced indices (``jnp.take`` with an
[BV, d_tile] index tile) — the interpret path (this container's
validation mode) handles that directly; real-TPU lowering would route
it through the dynamic-gather unit or fall back to per-row DMA.

Bit-exactness: both layouts compute exactly the packed JAX path's word
algebra (gather, AND, OR-reduce over the forward-slot axis, AND-NOT,
OR) — OR is associative/commutative so neither row-tile nor d-tile
order can matter, and zero padding is exact: padded vertex rows have
all-zero masks (hit 0), padded word lanes carry zero bits through
every op, padded ``fwd_nbr`` entries are pre-clipped to row 0 with a
zeroed mask, and the resident plane reserves a guaranteed all-zero row
at index ``rows`` for padded/invalid ``gidx`` entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitset
from repro.kernels import gain_core, vmem_budget

BLOCK_V = 128

# Invariants the static contract checker (repro.analysis) proves on a
# canonical fixture: one fused launch per BFS step (the launch sits in
# the sampler's while body), no aliasing, and no dtype outside this
# set (the key<fry> is the sampler's PRNG key threading through the
# trace — the kernel itself never sees it).
CONTRACT = dict(
    family="rrr_expand",
    launches=1,
    in_loop=True,
    dtypes=("bool", "float32", "int32", "key<fry>", "uint32"),
    aliases=(),
)


def _kernel(nbr_hbm, gmask_hbm, frontier_ref, visited_ref,
            newf_ref, visout_ref, hit_ref, nbr_buf, gm_buf,
            nbr_sem, gm_sem, *, block_v: int, d_tile: int,
            num_d_tiles: int, w: int):
    """Streamed-gmask layout: a whole packed BFS expansion step.

    nbr_hbm     int32  [ND * n_pad, DT]  HBM/ANY — streamed index tiles
    gmask_hbm   uint32 [ND * n_pad, GQ]  HBM/ANY — streamed mask tiles,
                                         (DT, w) flattened into one
                                         lane-padded axis (GQ =
                                         pad(DT*w, LANE)) so lane
                                         padding amortizes over the
                                         whole per-tile mask instead of
                                         inflating every slot's W words
                                         to a full lane
    frontier_ref uint32 [n_pad, Wp]      VMEM in (gathered at nbr tiles)
    visited_ref uint32 [n_pad, Wp]       VMEM in
    newf_ref    uint32 [n_pad, Wp]       VMEM out (next frontier)
    visout_ref  uint32 [n_pad, Wp]       VMEM out (visited | new)
    hit_ref     uint32 [BV, Wp]          d-tile OR-accumulator scratch
    nbr_buf     int32  [2, BV, DT]       double-buffered index scratch
    gm_buf      uint32 [2, BV, GQ]       double-buffered mask scratch

    Stream tile s covers row tile t = s // ND, forward-slot tile
    d_i = s % ND at row offset d_i * n_pad + t * BV; partial hits
    OR-accumulate in hit_ref and the new/visited updates fire on the
    last d-tile of each row tile.
    """
    n_pad, wp = frontier_ref.shape
    num_tiles = n_pad // block_v
    total = num_tiles * num_d_tiles

    def tile_dmas(slot, s):
        off = (jax.lax.rem(s, num_d_tiles) * n_pad
               + (s // num_d_tiles) * block_v)
        return (pltpu.make_async_copy(
                    nbr_hbm.at[pl.ds(off, block_v)],
                    nbr_buf.at[slot], nbr_sem.at[slot]),
                pltpu.make_async_copy(
                    gmask_hbm.at[pl.ds(off, block_v)],
                    gm_buf.at[slot], gm_sem.at[slot]))

    for dma in tile_dmas(0, 0):
        dma.start()

    def stream_body(s, _):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < total)
        def _prefetch():
            for dma in tile_dmas(jax.lax.rem(s + 1, 2), s + 1):
                dma.start()

        for dma in tile_dmas(slot, s):
            dma.wait()
        t = s // num_d_tiles
        d_i = jax.lax.rem(s, num_d_tiles)
        # gather + AND + OR-accumulate, all in VMEM tile scope
        gathered = jnp.take(frontier_ref[...], nbr_buf[slot],
                            axis=0)[:, :, :w]            # [BV, DT, w]
        gm = gm_buf[slot][:, :d_tile * w].reshape(block_v, d_tile, w)
        part = bitset.or_reduce(gathered & gm, axis=1)   # [BV, w]
        part = jnp.pad(part, ((0, 0), (0, wp - w)))

        @pl.when(d_i == 0)
        def _first():
            hit_ref[...] = part

        @pl.when(d_i > 0)
        def _accumulate():
            hit_ref[...] = hit_ref[...] | part

        @pl.when(d_i == num_d_tiles - 1)
        def _emit():
            vis = visited_ref[pl.ds(t * block_v, block_v), :]
            new = hit_ref[...] & ~vis
            newf_ref[pl.ds(t * block_v, block_v), :] = new
            visout_ref[pl.ds(t * block_v, block_v), :] = vis | new

        return 0

    jax.lax.fori_loop(0, total, stream_body, 0)


def _kernel_resident(nbr_hbm, gidx_hbm, plane_ref, frontier_ref,
                     visited_ref, newf_ref, visout_ref, hit_ref,
                     nbr_buf, gidx_buf, nbr_sem, gidx_sem, *,
                     block_v: int, num_d_tiles: int):
    """Resident coin-plane layout: BOTH gathers in-kernel.

    nbr_hbm     int32  [ND * n_pad, DT]  HBM/ANY — frontier row indices
    gidx_hbm    int32  [ND * n_pad, DT]  HBM/ANY — coin-plane row
                                         indices (nbr * d_pad +
                                         rev_slot; invalid slots point
                                         at the guaranteed zero row)
    plane_ref   uint32 [rows_pad, Wp]    VMEM in — the per-step packed
                                         coin-plane, resident all step
    frontier/visited/newf/visout/hit     as in the streamed kernel
    nbr_buf, gidx_buf int32 [2, BV, DT]  double-buffered index scratch

    No mask words move per tile — only index pairs stream; the gmask
    HBM round-trip of the streamed layout does not exist here.
    """
    n_pad, wp = frontier_ref.shape
    num_tiles = n_pad // block_v
    total = num_tiles * num_d_tiles

    def tile_dmas(slot, s):
        off = (jax.lax.rem(s, num_d_tiles) * n_pad
               + (s // num_d_tiles) * block_v)
        return (pltpu.make_async_copy(
                    nbr_hbm.at[pl.ds(off, block_v)],
                    nbr_buf.at[slot], nbr_sem.at[slot]),
                pltpu.make_async_copy(
                    gidx_hbm.at[pl.ds(off, block_v)],
                    gidx_buf.at[slot], gidx_sem.at[slot]))

    for dma in tile_dmas(0, 0):
        dma.start()

    def stream_body(s, _):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < total)
        def _prefetch():
            for dma in tile_dmas(jax.lax.rem(s + 1, 2), s + 1):
                dma.start()

        for dma in tile_dmas(slot, s):
            dma.wait()
        t = s // num_d_tiles
        d_i = jax.lax.rem(s, num_d_tiles)
        # both gathers + AND + OR-accumulate in VMEM tile scope
        gathered = jnp.take(frontier_ref[...], nbr_buf[slot],
                            axis=0)                      # [BV, DT, Wp]
        gm = jnp.take(plane_ref[...], gidx_buf[slot],
                      axis=0)                            # [BV, DT, Wp]
        part = bitset.or_reduce(gathered & gm, axis=1)   # [BV, Wp]

        @pl.when(d_i == 0)
        def _first():
            hit_ref[...] = part

        @pl.when(d_i > 0)
        def _accumulate():
            hit_ref[...] = hit_ref[...] | part

        @pl.when(d_i == num_d_tiles - 1)
        def _emit():
            vis = visited_ref[pl.ds(t * block_v, block_v), :]
            new = hit_ref[...] & ~vis
            newf_ref[pl.ds(t * block_v, block_v), :] = new
            visout_ref[pl.ds(t * block_v, block_v), :] = vis | new

        return 0

    jax.lax.fori_loop(0, total, stream_body, 0)


def _d_stream(x, n_pad: int, nd: int, lane_cols: int | None = None,
              fill=0):
    """Lay a [n_pad, nd * cols] per-vertex array out as the d-tiled
    stream [nd * n_pad, cols]: tile (t, d_i) of the kernel loop reads
    rows [d_i * n_pad + t*BV, ...) — one contiguous ``pl.ds`` slice."""
    cols = x.shape[1] // nd
    x = x.reshape(n_pad, nd, cols)
    if lane_cols is not None and lane_cols != cols:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, lane_cols - cols)),
                    constant_values=fill)
    return jnp.transpose(x, (1, 0, 2)).reshape(nd * n_pad, -1)


def _geometry(n: int, w: int, block_v):
    return vmem_budget._sampler_geometry(n, w, block_v)


@functools.partial(jax.jit, static_argnames=(
    "block_v", "d_tile", "vmem_budget_bytes", "interpret"))
def rrr_expand_step_pallas(frontier: jnp.ndarray, visited: jnp.ndarray,
                           fwd_nbr: jnp.ndarray, gmask: jnp.ndarray,
                           block_v: int | None = None,
                           d_tile: int | None = None,
                           vmem_budget_bytes: int | None = None,
                           interpret: bool = False):
    """Fused packed BFS expansion step, streamed-gmask layout:

      frontier uint32 [n, W], visited uint32 [n, W],
      fwd_nbr  int32  [n, df]    (pad entries pre-clipped to 0),
      gmask    uint32 [n, df, W] (zero at padded forward slots)
      -> (new_frontier uint32 [n, W], new_visited uint32 [n, W])

    in a single pallas_call; bit-identical to the packed JAX path

      hit = or_reduce(frontier[fwd_nbr] & gmask, axis=1)
      new = hit & ~visited;  new_visited = visited | new.

    ``block_v``/``d_tile`` default to the ``kernels.vmem_budget``
    policies (tuned table, then the analytic VMEM solve — the d_out
    axis tiles into the stream whenever 2·BV·d_out·W would overflow
    the budget; neither knob affects results).  Zero padding is exact
    (see module docstring); d_out = 0 graphs short-circuit to an empty
    expansion.
    """
    n, w = frontier.shape
    df = fwd_nbr.shape[1]
    if df == 0:   # edgeless graph: nothing can fire
        return jnp.zeros_like(frontier), visited
    bv, n_pad, wp = _geometry(n, w, block_v)
    dt = d_tile if d_tile is not None else vmem_budget.sampler_d_tile(
        df, w, block_v=bv, n_pad=n_pad, resident=False,
        vmem_budget_bytes=vmem_budget_bytes)
    dt = max(1, min(int(dt), df))
    nd = -(-df // dt)
    dfp = nd * dt
    # The mask stream flattens (dt, w) into one lane axis before
    # padding: GQ = pad(dt*w, LANE), so the dominant per-step tensor
    # carries at most one lane of zero padding per vertex tile instead
    # of padding every slot's W words to 128.
    gq = gain_core.padded_size(dt * w, gain_core.LANE)
    gmask = jnp.pad(gmask, ((0, n_pad - n), (0, dfp - df), (0, 0)))
    gmask = _d_stream(gmask.reshape(n_pad, dfp * w), n_pad, nd,
                      lane_cols=gq)
    fwd_nbr = jnp.pad(fwd_nbr, ((0, n_pad - n), (0, dfp - df)))
    fwd_nbr = _d_stream(fwd_nbr, n_pad, nd)
    if n_pad != n or wp != w:
        frontier = jnp.pad(frontier, ((0, n_pad - n), (0, wp - w)))
        visited = jnp.pad(visited, ((0, n_pad - n), (0, wp - w)))
    newf, viso = pl.pallas_call(
        functools.partial(_kernel, block_v=bv, d_tile=dt,
                          num_d_tiles=nd, w=w),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, wp), frontier.dtype),
            jax.ShapeDtypeStruct((n_pad, wp), frontier.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bv, wp), frontier.dtype),      # hit accumulator
            pltpu.VMEM((2, bv, dt), jnp.int32),        # index double buf
            pltpu.VMEM((2, bv, gq), frontier.dtype),   # mask double buf
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(fwd_nbr, gmask, frontier, visited)
    return newf[:n, :w], viso[:n, :w]


@functools.partial(jax.jit, static_argnames=(
    "block_v", "d_tile", "vmem_budget_bytes", "interpret"))
def rrr_expand_step_resident_pallas(frontier: jnp.ndarray,
                                    visited: jnp.ndarray,
                                    fwd_nbr: jnp.ndarray,
                                    gidx: jnp.ndarray,
                                    plane: jnp.ndarray,
                                    block_v: int | None = None,
                                    d_tile: int | None = None,
                                    vmem_budget_bytes: int | None = None,
                                    interpret: bool = False):
    """Fused packed BFS expansion step, resident coin-plane layout:

      frontier uint32 [n, W], visited uint32 [n, W],
      fwd_nbr  int32  [n, df]    (pad entries pre-clipped to 0),
      gidx     int32  [n, df]    coin-plane row per forward slot
                                 (values in [0, rows]; ``rows`` itself
                                 reads a guaranteed all-zero row — the
                                 caller's sentinel for invalid slots),
      plane    uint32 [rows, W]  the per-step packed coin-plane
      -> (new_frontier uint32 [n, W], new_visited uint32 [n, W])

    in a single pallas_call, bit-identical to the streamed layout and
    the packed JAX path: the kernel computes

      hit = or_reduce(frontier[fwd_nbr] & plane[gidx], axis=1)
      new = hit & ~visited;  new_visited = visited | new

    with BOTH gathers inside the launch — no [n, df, W] gmask is ever
    built, on the XLA side or anywhere else.  ``block_v``/``d_tile``
    default to the ``kernels.vmem_budget`` policies.
    """
    n, w = frontier.shape
    df = fwd_nbr.shape[1]
    if df == 0:   # edgeless graph: nothing can fire
        return jnp.zeros_like(frontier), visited
    rows = plane.shape[0]
    bv, n_pad, wp = _geometry(n, w, block_v)
    # Pad the plane past rows+1 so index ``rows`` is a real, all-zero
    # row even when rows is already sublane-aligned.
    rows_pad = gain_core.padded_size(rows + 1, gain_core.SUBLANE)
    dt = d_tile if d_tile is not None else vmem_budget.sampler_d_tile(
        df, w, block_v=bv, n_pad=n_pad, resident=True,
        plane_rows=rows_pad, vmem_budget_bytes=vmem_budget_bytes)
    dt = max(1, min(int(dt), df))
    nd = -(-df // dt)
    dfp = nd * dt
    plane = jnp.pad(plane, ((0, rows_pad - rows), (0, wp - w)))
    fwd_nbr = jnp.pad(fwd_nbr, ((0, n_pad - n), (0, dfp - df)))
    fwd_nbr = _d_stream(fwd_nbr, n_pad, nd)
    gidx = jnp.pad(gidx, ((0, n_pad - n), (0, dfp - df)),
                   constant_values=rows)
    gidx = _d_stream(gidx, n_pad, nd)
    if n_pad != n or wp != w:
        frontier = jnp.pad(frontier, ((0, n_pad - n), (0, wp - w)))
        visited = jnp.pad(visited, ((0, n_pad - n), (0, wp - w)))
    newf, viso = pl.pallas_call(
        functools.partial(_kernel_resident, block_v=bv, num_d_tiles=nd),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, wp), frontier.dtype),
            jax.ShapeDtypeStruct((n_pad, wp), frontier.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bv, wp), frontier.dtype),      # hit accumulator
            pltpu.VMEM((2, bv, dt), jnp.int32),        # nbr double buf
            pltpu.VMEM((2, bv, dt), jnp.int32),        # gidx double buf
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(fwd_nbr, gidx, plane, frontier, visited)
    return newf[:n, :w], viso[:n, :w]
