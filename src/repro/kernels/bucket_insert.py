"""Pallas TPU kernels: fused + pipelined streaming-receiver insertion.

The legacy receiver (``streaming.insert_chunk`` with a ``lax.scan``)
launches one ``bucket_gains`` pallas_call per streamed candidate and
round-trips the [B, W] bucket covers through HBM on every step — O(C)
kernel launches and O(C * B * W) words of HBM traffic per chunk.  Two
kernels replace it, sharing one in-kernel insertion body:

``bucket_insert_chunk_pallas`` (PR 1) streams a whole chunk of C
candidate rows [C, W] through all B threshold buckets *in arrival
order* inside a single pallas_call:

  * the bucket covers are loaded into VMEM once and stay resident
    across the in-kernel candidate loop (one HBM read + one write per
    chunk instead of two per candidate);
  * per candidate, the marginal gains, the threshold/count accept
    decision, the cover OR-update, and the seed-slot write are all
    fused on the VPU (buckets ride the sublane axis, words the lane
    axis);
  * the word axis is tiled (``block_w`` lanes at a time) so arbitrary
    W only ever touches one [B, block_w] tile of covers per step;
  * candidate seed ids are scalar-fetched from SMEM; the per-bucket
    admission counts ride the candidate loop carry (scalar registers),
    thresholds sit in a tiny [B, 1] block.

``bucket_insert_stream_pallas`` (PR 2) extends this to a whole
multi-chunk candidate stream [R, C, W] in ONE pallas_call: the stream
stays in HBM/ANY memory, the covers / seeds / counts live in VMEM for
the *entire* stream, and ``pltpu.make_async_copy`` double-buffers the
HBM->VMEM load of chunk r+1's rows into a [2, C, W] VMEM scratch while
chunk r inserts — the in-kernel analogue of the paper's nonblocking
streaming overlap of transfer with insertion.

HBM-traffic model per stream of R chunks x C candidates (T = R*C):

  scan       T * (2*B*W + W) words,   T launches
  fused      R * 2*B*W + T*W words,   R launches (covers round-trip
                                      between chunks)
  pipelined  2*B*W + T*W     words,   1 launch, chunk r+1 DMA hidden
                                      behind chunk r's insertion

Exact arrival-order semantics (and hence bit-identical
``StreamState``) are preserved by all paths: candidate c+1 sees the
covers as updated by candidate c, across chunk boundaries too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gain_core

BLOCK_W = 512

# Static contract (proved by repro.analysis on a canonical fixture).
# Both receiver variants stage exactly one top-level launch: the chunk
# kernel per [C, W] chunk, the pipelined stream kernel per whole
# [R, C, W] stream (float32 is the bucket thresholds).
CONTRACT = dict(
    family="bucket_insert",
    dtypes=("bool", "float32", "int32", "uint32"),
    aliases=(),
    variants=dict(
        chunk=dict(launches=1, in_loop=False),
        stream=dict(launches=1, in_loop=False),
    ),
)

# The chunk-size VMEM solve lives in ``kernels.vmem_budget``
# (``receiver_chunk_size``) — the single budget model shared with the
# sampler/sender tile solves and the autotuner.


def _padded_w(w: int, block_w: int = BLOCK_W) -> tuple[int, int]:
    """(effective block_w, W padded up to a whole number of blocks)."""
    bw = gain_core.effective_block(w, block_w, gain_core.LANE)
    return bw, gain_core.padded_size(w, bw)


def _insert_candidates(read_id, read_row_tile, c_total, covers_ref,
                       seeds_ref, thr_ref, counts, *, block_w: int,
                       num_word_tiles: int, lane):
    """Arrival-order insertion of ``c_total`` candidates into the
    VMEM-resident bucket state — the body shared by the fused-chunk
    and pipelined-stream kernels.

    read_id(c)          -> int32 scalar candidate id
    read_row_tile(c, s) -> uint32 [1, block_w] row words at offset s
    counts              int32 [B, 1] loop carry
    """

    def insert_one(c, counts):
        sid = read_id(c)

        # Pass 1 over word tiles: marginal gain of candidate c against
        # every bucket's running cover.
        def gain_tile(t, acc):
            s = t * block_w
            row_t = read_row_tile(c, s)                        # [1, bw]
            cov_t = covers_ref[:, pl.ds(s, block_w)]           # [B, bw]
            return acc + gain_core.gain_tile_sum(row_t, cov_t)

        gains = jax.lax.fori_loop(
            0, num_word_tiles, gain_tile,
            jnp.zeros(counts.shape, dtype=jnp.int32))          # [B, 1]

        # Accept decision (Algorithm 5 line 6): valid id, bucket not
        # full, gain clears the bucket's guess_b / (2k) threshold.
        k = seeds_ref.shape[1]
        accept = ((sid >= 0) & (counts < k)
                  & (gains.astype(jnp.float32) >= thr_ref[...]))

        # Pass 2: OR the candidate row into every accepting cover.
        def or_tile(t, _):
            s = t * block_w
            row_t = read_row_tile(c, s)
            cov_t = covers_ref[:, pl.ds(s, block_w)]
            covers_ref[:, pl.ds(s, block_w)] = jnp.where(
                accept, cov_t | row_t, cov_t)
            return 0

        jax.lax.fori_loop(0, num_word_tiles, or_tile, 0)

        # Seed-slot write: counts < k is part of accept, so the write
        # slot clip(counts, 0, k-1) can never overwrite a full bucket.
        slot = jnp.clip(counts, 0, k - 1)                      # [B, 1]
        hit = accept & (lane == slot)                          # [B, k]
        seeds_ref[...] = jnp.where(hit, sid, seeds_ref[...])
        return counts + accept.astype(jnp.int32)

    return jax.lax.fori_loop(0, c_total, insert_one, counts)


def _kernel(ids_ref, thr_ref, counts_in_ref, rows_ref, covers_in_ref,
            seeds_in_ref, covers_ref, seeds_ref, counts_out_ref, *,
            block_w: int):
    b, w = covers_ref.shape
    c_total = rows_ref.shape[0]
    k = seeds_ref.shape[1]

    # Materialize the running state in the output blocks once; they
    # stay VMEM-resident across the whole candidate loop.
    covers_ref[...] = covers_in_ref[...]
    seeds_ref[...] = seeds_in_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    counts = _insert_candidates(
        lambda c: ids_ref[0, c],
        lambda c, s: rows_ref[pl.ds(c, 1), pl.ds(s, block_w)],
        c_total, covers_ref, seeds_ref, thr_ref,
        counts_in_ref[...], block_w=block_w,
        num_word_tiles=w // block_w, lane=lane)
    counts_out_ref[...] = counts


def _stream_kernel(ids_ref, thr_ref, counts_in_ref, stream_ref,
                   covers_in_ref, seeds_in_ref, covers_ref, seeds_ref,
                   counts_out_ref, rows_buf, ids_buf, row_sem, id_sem,
                   *, block_w: int, c_chunk: int):
    """Multi-chunk pipelined receiver: the [R, C, W] candidate stream
    and its [R, C] ids stay in HBM/ANY; double-buffered
    ``make_async_copy``s pull chunk r+1's rows into the [2, C, W] VMEM
    scratch (and its ids into the [2, 1, C] SMEM scratch — only one
    chunk's ids are ever scalar-resident, so SMEM pressure is O(C),
    not O(R*C)) while the shared insertion body consumes chunk r.
    Covers / seeds / counts never leave VMEM between chunks."""
    b, w = covers_ref.shape
    r_total = stream_ref.shape[0]
    k = seeds_ref.shape[1]

    covers_ref[...] = covers_in_ref[...]
    seeds_ref[...] = seeds_in_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def chunk_dma(slot, r):
        return (pltpu.make_async_copy(stream_ref.at[r],
                                      rows_buf.at[slot],
                                      row_sem.at[slot]),
                pltpu.make_async_copy(ids_ref.at[r], ids_buf.at[slot],
                                      id_sem.at[slot]))

    # Warm up: chunk 0 starts loading before the loop.
    for dma in chunk_dma(0, 0):
        dma.start()

    def chunk_body(r, counts):
        slot = jax.lax.rem(r, 2)

        # Kick off chunk r+1's HBM->VMEM/SMEM copies into the other
        # buffer; they land while chunk r's candidates insert below.
        @pl.when(r + 1 < r_total)
        def _():
            for dma in chunk_dma(jax.lax.rem(r + 1, 2), r + 1):
                dma.start()

        for dma in chunk_dma(slot, r):
            dma.wait()
        return _insert_candidates(
            lambda c: ids_buf[slot, 0, c],
            lambda c, s: rows_buf[slot, pl.ds(c, 1), pl.ds(s, block_w)],
            c_chunk, covers_ref, seeds_ref, thr_ref, counts,
            block_w=block_w, num_word_tiles=w // block_w, lane=lane)

    counts = jax.lax.fori_loop(0, r_total, chunk_body,
                               counts_in_ref[...])
    counts_out_ref[...] = counts


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def bucket_insert_chunk_pallas(seed_ids: jnp.ndarray, rows: jnp.ndarray,
                               covers: jnp.ndarray, counts: jnp.ndarray,
                               seeds: jnp.ndarray,
                               thresholds: jnp.ndarray,
                               block_w: int = BLOCK_W,
                               interpret: bool = False):
    """Insert a chunk of candidates into all buckets, fused.

    seed_ids   int32   [C]     candidate ids (-1 = padding, skipped)
    rows       uint32  [C, W]  packed covering sets, arrival order
    covers     uint32  [B, W]  running bucket covers
    counts     int32   [B]     seeds admitted per bucket
    seeds      int32   [B, k]  admitted seed ids (-1 pad)
    thresholds float32 [B]     admission thresholds guess_b / (2k)

    Returns (covers, counts, seeds) updated — bit-identical to folding
    ``streaming._insert_one`` over the chunk in order.
    """
    b, w = covers.shape
    bw, wp = _padded_w(w, block_w)
    if wp != w:
        # Zero padding is exact: padded row words contribute popcount 0
        # to gains and OR identity to covers.
        rows = jnp.pad(rows, ((0, 0), (0, wp - w)))
        covers = jnp.pad(covers, ((0, 0), (0, wp - w)))
    covers_out, seeds_out, counts_out = pl.pallas_call(
        functools.partial(_kernel, block_w=bw),
        name="bucket_insert_chunk",
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # seed ids [1, C]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # thresholds [B, 1]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # counts in  [B, 1]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # rows   [C, Wp]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # covers [B, Wp]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # seeds  [B, k]
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(covers.shape, covers.dtype),
            jax.ShapeDtypeStruct(seeds.shape, seeds.dtype),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(seed_ids[None, :].astype(jnp.int32), thresholds[:, None],
      counts[:, None], rows, covers, seeds)
    return covers_out[:, :w], counts_out[:, 0], seeds_out


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def bucket_insert_stream_pallas(seed_ids: jnp.ndarray, rows: jnp.ndarray,
                                covers: jnp.ndarray, counts: jnp.ndarray,
                                seeds: jnp.ndarray,
                                thresholds: jnp.ndarray,
                                block_w: int = BLOCK_W,
                                interpret: bool = False):
    """Insert a whole multi-chunk candidate stream, pipelined.

    seed_ids   int32   [R, C]     candidate ids (-1 = padding, skipped)
    rows       uint32  [R, C, W]  packed covering sets, arrival order
    covers     uint32  [B, W]     running bucket covers
    counts     int32   [B]        seeds admitted per bucket
    seeds      int32   [B, k]     admitted seed ids (-1 pad)
    thresholds float32 [B]        admission thresholds guess_b / (2k)

    One pallas_call for the entire stream: the rows stay in HBM/ANY,
    covers / seeds / counts stay VMEM-resident across all R chunks,
    and chunk r+1's rows DMA in (double-buffered) while chunk r
    inserts.  Returns (covers, counts, seeds) updated — bit-identical
    to folding ``bucket_insert_chunk_pallas`` over the R chunks, which
    is itself bit-identical to the legacy per-candidate scan.
    """
    b, w = covers.shape
    r, c = seed_ids.shape
    if r == 0:
        return covers, counts, seeds
    bw, wp = _padded_w(w, block_w)
    # Each chunk is DMA'd on its own, and Mosaic refuses a slice that
    # is not a whole number of tiles: pad each chunk's rows to whole
    # sublane tiles and its ids to whole lane tiles, and give each
    # chunk's ids a unit axis so that the slice is on an untiled
    # leading axis.  The kernel loops over the C real candidates
    # only, so pads are never read.
    cs = gain_core.padded_size(c, gain_core.SUBLANE)
    cp = gain_core.padded_size(c, gain_core.LANE)
    if wp != w or cs != c:
        rows = jnp.pad(rows, ((0, 0), (0, cs - c), (0, wp - w)))
    if wp != w:
        covers = jnp.pad(covers, ((0, 0), (0, wp - w)))
    ids = jnp.pad(seed_ids.astype(jnp.int32), ((0, 0), (0, cp - c)),
                  constant_values=-1)[:, None, :]
    covers_out, seeds_out, counts_out = pl.pallas_call(
        functools.partial(_stream_kernel, block_w=bw, c_chunk=c),
        name="bucket_insert_stream",
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),        # ids [R, 1, Cp]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # thresholds [B, 1]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # counts in  [B, 1]
            pl.BlockSpec(memory_space=pl.ANY),        # stream [R, Cs, Wp]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # covers [B, Wp]
            pl.BlockSpec(memory_space=pltpu.VMEM),    # seeds  [B, k]
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(covers.shape, covers.dtype),
            jax.ShapeDtypeStruct(seeds.shape, seeds.dtype),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, cs, wp), rows.dtype),      # rows double buf
            pltpu.SMEM((2, 1, cp), jnp.int32),        # ids double buf
            pltpu.SemaphoreType.DMA((2,)),            # rows sems
            pltpu.SemaphoreType.DMA((2,)),            # ids sems
        ],
        interpret=interpret,
    )(ids, thresholds[:, None], counts[:, None], rows, covers, seeds)
    return covers_out[:, :w], counts_out[:, 0], seeds_out
