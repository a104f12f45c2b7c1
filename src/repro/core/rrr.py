"""Batched Random-Reverse-Reachable (RRR) set sampling.

TPU adaptation of the paper's per-rank probabilistic BFS (§3.4 S1).
Three execution paths share bit-identical semantics (same PRNG key ⇒
identical packed incidence), the ``sampler=`` analogue of the sender's
``solver=`` quad:

  * ``sampler="dense"``  — frontier/visited state of a *batch* of
    samples is a dense bool matrix ``[batch, n]`` and one BFS expansion
    is a fused gather/coin-flip/scatter over the padded reverse
    adjacency (``hit.at[...].max``).  The reference path.
  * ``sampler="packed"`` — frontier/visited live as word-packed uint32
    ``[n, batch/32]`` for the whole BFS (32 samples per word, 8x fewer
    state bytes than bool) and the expansion is a *gather* over the
    padded **forward** adjacency:
    ``hit_word[u] |= frontier_word[v] & coin_mask_word[v, rev_slot]``
    for every forward pair ``(v, rev_slot)`` of ``u``.  Coin masks are
    the dense path's per-step coins packed over the batch lane — coins
    are drawn with the exact same keys/shapes/order, so
    ``pack(visited_dense.T) == visited_packed`` bit-for-bit.  The
    sampled incidence ``[n, W]`` is emitted directly: the ``[theta, n]``
    bool intermediate and the final ``pack_bool_matrix(vis.T)``
    transpose of the dense path disappear.
  * ``sampler="kernel"`` — the packed path with the hot expansion step
    fused into ONE Pallas launch per BFS step
    (``repro.kernels.rrr_expand``), in one of two gather layouts
    (``gather=``, default ``"auto"`` — a VMEM-budget solve): with
    ``"resident"`` the per-step packed coin-plane
    (uint32 [n·d_pad, W]) stays VMEM-resident and only int32
    ``(fwd_nbr, gidx)`` index tiles stream, so BOTH gathers (frontier
    rows, coin words at ``rev_slot``) happen inside the kernel — the
    XLA-side [n, d_out, W] gmask gather and its HBM round-trip never
    exist; with ``"streamed"`` (the fallback when the coin-plane
    exceeds VMEM) XLA pre-gathers the mask tiles and the kernel
    streams (fwd_nbr, gmask) pairs double-buffered.  Either way
    gather + AND + OR-accumulate + the new/visited updates fuse so
    the gathered ``[n, d_out, W]`` frontier intermediate never
    touches HBM, heavy-hub forward rows tile into the stream
    (order-free OR), and both layouts are bit-exact to the packed
    JAX path (identical word algebra).

Each expansion re-draws edge coins; under IC an edge is examined
exactly once (its source is in the frontier exactly once), so per-step
redraws are distributionally identical to a live-edge graph.

Under IC the packed samplers draw coins for the frontier, not for the
graph.  Every coin is a pure function of its key and its flat index
in the dense path's ``[batch, n, chunk]`` draw (JAX's partitionable
Threefry), so a BFS step whose frontier holds at most a fixed
capacity of (sample, vertex) pairs (``_frontier_capacity``: twice the
batch, in whole update blocks) computes only the coins of those pairs'
in-edge slots, ORs the fired ``(sample, in-neighbor)`` bits into the
state after sorting out duplicates, and never builds the step's coin
plane.  A step with a larger frontier (supercritical IC) takes the
dense step below through ``lax.cond``; both draw the same coins, so
the branch never changes a result.  ``sampler="kernel"`` uses its
kernel only in that fallback.

LT uses the live-edge equivalence of Kempe et al.: every vertex selects
at most one incoming edge (with probability = its weight); the RRR set
is the chain of selected in-neighbors — this is why LT traversals are
shallower, matching the paper's observation (§4.2).  The packed LT
expansion reuses the IC machinery with the coin mask replaced by the
packed one-hot edge-selection mask, so both models share one gather
engine (and one Pallas kernel).  LT steps are always dense.

``coin_chunk`` bounds the dense IC coin draw (and the LT selection-mask
pack) to ``[batch, n, coin_chunk]`` slots at a time, so the bool coin
intermediate is O(batch * n * coin_chunk) — not O(batch * n * d_max)
— on every sampler; essential for skewed-degree graphs.  The packed
samplers' dense step additionally accumulates the word-packed
``[n, d_max, batch/32]`` per-step slot mask (each chunk packs over
the batch lane immediately, so the mask costs batch/8 bytes per edge
slot — 1/8 of an unchunked bool mask — but its d_max axis is *not*
bounded by coin_chunk; on extreme-degree graphs the dense sampler is
currently the lower-peak-memory choice).  That fallback stays
compiled in, so it sets the packed samplers' peak memory even where
every step takes the frontier path, whose own buffers are
O(capacity * d_max) plus the packed state.  The chunk width is part
of the PRNG stream under IC (coins fold in the chunk index), so it
acts like a seed: dense/packed/kernel parity holds at any fixed
value, but changing it changes the sampled sets.
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p

from repro.core import bitset
from repro.graphs.csr import (CSRGraph, padded_adjacency,
                              padded_forward_adjacency)

Model = Literal["IC", "LT"]

SAMPLERS = ("dense", "packed", "kernel")


def resolve_sampler(sampler: Optional[str], default: str = "dense") -> str:
    """Validate the S1 sampler triad (mirrors ``maxcover.resolve_solver``)."""
    if sampler is None:
        sampler = default
    if sampler not in SAMPLERS:
        raise ValueError(
            f"unknown sampler {sampler!r}; expected one of {SAMPLERS}")
    return sampler


def _require_fwd(fwd, sampler: str):
    if fwd is None:
        raise ValueError(
            f"sampler={sampler!r} needs fwd=(fwd_nbr, fwd_rslot) — the "
            "padded forward adjacency from "
            "repro.graphs.csr.padded_forward_adjacency(g)")
    return fwd


def _coin_chunks(d: int, coin_chunk: int) -> Tuple[int, int, int]:
    """(chunk, n_chunks, d_pad) of the degree-chunked coin draw."""
    if coin_chunk < 1:
        raise ValueError(f"coin_chunk must be >= 1, got {coin_chunk}")
    chunk = min(d, coin_chunk)
    n_chunks = (d + chunk - 1) // chunk
    return chunk, n_chunks, n_chunks * chunk


def coins_per_step(n: int, d: int, batch: int, *, model: str,
                   coin_chunk: int) -> int:
    """Uniforms every sampler draws in one BFS step of a batch, whatever
    its frontier holds: under IC one per (sample, vertex, edge slot) of
    the degree-chunked ``[batch, n, d_pad]`` coin plane, under LT one
    per (sample, vertex)."""
    if d == 0:
        return 0
    if model == "IC":
        return batch * n * _coin_chunks(d, coin_chunk)[2]
    return batch * n


class S1Counts(NamedTuple):
    """What one sampler call did."""
    bfs_steps: jnp.ndarray       # int32: BFS steps taken
    frontier_steps: jnp.ndarray  # int32: steps that drew for the frontier
    # float32: uniforms drawn (one dense step can pass int32's range);
    # a frontier step counts d_pad per frontier pair, not the padding
    # of its fixed-capacity buffers
    coins_drawn: jnp.ndarray


def _dense_counts(steps, n: int, d: int, batch: int, *, model: str,
                  coin_chunk: int) -> S1Counts:
    """The counts of ``steps`` dense steps."""
    plane = coins_per_step(n, d, batch, model=model, coin_chunk=coin_chunk)
    return S1Counts(steps, jnp.int32(0),
                    steps.astype(jnp.float32) * np.float32(plane))


@functools.partial(
    jax.jit, static_argnames=("model", "max_steps", "sampler", "coin_chunk",
                              "gather", "block_v"))
def rrr_batch(nbr, prob, wt, roots, key, *, model: str, max_steps: int = 64,
              sampler: str = "dense", fwd=None, coin_chunk: int = 32,
              gather: str = "auto", block_v: Optional[int] = None):
    """Generate one batch of RRR sets.

    Args:
      nbr/prob/wt: padded reverse adjacency [n, d] (row v = in-nbrs of v).
      roots: int32 [batch] source vertices (chosen uniformly by caller).
      key: PRNG key.
      sampler: "dense" | "packed" | "kernel" (see module docstring).
        The packed paths need ``fwd=(fwd_nbr, fwd_rslot)`` and return
        the *same* dense bool matrix (unpacked from the word state) —
        a parity/compat shim; the memory win lives in
        :func:`sample_incidence`, which keeps the words packed.
      coin_chunk: IC coin-draw slot width (peak coin memory is
        O(batch * n * coin_chunk); part of the PRNG stream — see
        module docstring).
    Returns:
      visited: bool [batch, n]; visited[i, v] <=> v in RRR(roots[i]).
    """
    sampler = resolve_sampler(sampler)
    if sampler != "dense":
        fwd_nbr, fwd_rslot = _require_fwd(fwd, sampler)
        packed, _ = _rrr_batch_packed(
            nbr, prob, wt, fwd_nbr, fwd_rslot, roots, key, model=model,
            max_steps=max_steps, coin_chunk=coin_chunk,
            kernel=(sampler == "kernel"), gather=gather, block_v=block_v)
        return bitset.unpack_words(packed, roots.shape[0]).T
    return _rrr_batch_dense(nbr, prob, wt, roots, key, model=model,
                            max_steps=max_steps, coin_chunk=coin_chunk)[0]


def _rrr_batch_dense(nbr, prob, wt, roots, key, *, model: str,
                     max_steps: int, coin_chunk: int):
    """The dense BFS engine: (visited bool [batch, n], S1Counts)."""
    n, d = nbr.shape
    batch = roots.shape[0]
    visited0 = jnp.zeros((batch, n), dtype=bool).at[
        jnp.arange(batch), roots].set(True)
    if d == 0:          # edgeless graph: RRR(root) = {root}
        return visited0, _dense_counts(jnp.int32(0), n, d, batch,
                                       model=model, coin_chunk=coin_chunk)

    valid = nbr >= 0

    if model == "IC":
        # degree-chunked expansion: coins are drawn [batch, n, CHUNK]
        # at a time so peak memory is O(batch * n * CHUNK), not
        # O(batch * n * d_max) — essential for skewed-degree graphs.
        chunk, n_chunks, d_pad = _coin_chunks(d, coin_chunk)
        if d_pad != d:
            prob_p = jnp.pad(prob, ((0, 0), (0, d_pad - d)))
            tgt_p = jnp.pad(jnp.where(valid, nbr, n),
                            ((0, 0), (0, d_pad - d)), constant_values=n)
        else:
            prob_p = prob
            tgt_p = jnp.where(valid, nbr, n)

        def body(state):
            frontier, visited, k, step = state
            k, sub = jax.random.split(k)

            def slot_chunk(c, hit):
                coins = jax.random.uniform(
                    jax.random.fold_in(sub, c), (batch, n, chunk))
                p_c = lax.dynamic_slice(prob_p, (0, c * chunk),
                                        (n, chunk))
                t_c = lax.dynamic_slice(tgt_p, (0, c * chunk),
                                        (n, chunk))
                # v in frontier examines incoming edge (u -> v): with
                # prob p the reverse traversal reaches u.
                fire = frontier[:, :, None] & (coins < p_c[None])
                return hit.at[:, t_c.reshape(-1)].max(
                    fire.reshape(batch, -1))

            hit = jnp.zeros((batch, n + 1), dtype=bool)
            hit = lax.fori_loop(0, n_chunks, slot_chunk, hit)[:, :n]
            new = hit & ~visited
            return new, visited | new, k, step + 1
    else:  # LT live-edge: newly reached v follows exactly one in-edge,
        # edge j selected with prob wt[v, j] (possibly none).
        cumw = jnp.cumsum(wt, axis=1)  # [n, d]

        def body(state):
            frontier, visited, k, step = state
            k, sub = jax.random.split(k)
            r = jax.random.uniform(sub, (batch, n))
            # chosen slot = first j with r < cumw[v, j]; d means "none".
            chosen = jnp.sum(r[:, :, None] >= cumw[None], axis=-1)  # [b, n]
            has_pick = chosen < jnp.sum(valid, axis=1)[None]
            safe = jnp.clip(chosen, 0, d - 1)
            # gather one in-neighbor per (sample, vertex) without
            # materializing [b, n, d]
            pick_nbr = nbr[jnp.arange(n)[None, :], safe]
            go = frontier & has_pick & (pick_nbr >= 0)
            idx = jnp.where(go, pick_nbr, n)
            hit = jnp.zeros((batch, n + 1), dtype=bool).at[
                jnp.arange(batch)[:, None], idx].max(go)[:, :n]
            new = hit & ~visited
            return new, visited | new, k, step + 1

    def cond(state):
        frontier, _, _, step = state
        return jnp.any(frontier) & (step < max_steps)

    _, visited, _, steps = jax.lax.while_loop(
        cond, body, (visited0, visited0, key, jnp.int32(0)))
    return visited, _dense_counts(steps, n, d, batch, model=model,
                                  coin_chunk=coin_chunk)


def _packed_roots(roots, n: int):
    """Packed root incidence: bit i of word i//32 set at row roots[i].

    Scatter-add of distinct single-bit contributions — each sample is
    one unique bit, so add == OR even when roots repeat.
    """
    batch = roots.shape[0]
    w = bitset.num_words(batch)
    i = jnp.arange(batch)
    contrib = jnp.uint32(1) << (i % bitset.WORD_BITS).astype(jnp.uint32)
    return jnp.zeros((n, w), dtype=bitset.WORD_DTYPE).at[
        roots, i // bitset.WORD_BITS].add(contrib)


def _pack_batch_lane(fire, n: int, chunk: int, batch: int):
    """Pack a bool [batch, n, chunk] slot-mask over its batch axis
    into uint32 words [n, chunk, W]: bit j of word w at [v, slot] is
    fire[w*32+j, v, slot]."""
    w = bitset.num_words(batch)
    flat = fire.transpose(1, 2, 0).reshape(n * chunk, batch)
    return bitset.pack_bool_matrix(flat).reshape(n, chunk, w)


def _expand_packed(frontier, visited, fwd_nbr, fwd_rslot, mask,
                   kernel: bool, gather: str = "auto",
                   block_v: Optional[int] = None):
    """One packed BFS expansion: gather over the forward adjacency.

    frontier/visited: uint32 [n, W] packed state.
    mask: uint32 [n, d_pad, W] per-step packed coin/selection masks
      (bit b of mask[v, slot] = "sample b's traversal crosses reverse
      edge slot ``slot`` of v this step").
    Returns (new, visited | new).

    The ``kernel`` path fuses the expansion into one Pallas launch per
    step.  Under ``gather="resident"`` the mask goes in whole as the
    flat coin-plane [n * d_pad, W] and BOTH gathers (frontier rows at
    ``fwd_nbr``, coin words at ``gidx = fwd_nbr * d_pad + rev_slot``)
    happen inside the kernel — no [n, d_out, W] gmask is built
    anywhere.  Under ``"streamed"`` (the fallback when the coin-plane
    exceeds the VMEM budget; ``"auto"`` solves which) the gmask is
    pre-gathered here in XLA and streamed tile-by-tile, with only the
    frontier gather fused.  The JAX path mirrors the streamed layout.
    """
    valid = fwd_nbr >= 0
    nbr_c = jnp.where(valid, fwd_nbr, 0)
    if kernel:
        from repro.kernels import ops as kops
        from repro.kernels import vmem_budget
        n, d_pad, _ = mask.shape
        mode = vmem_budget.resolve_gather(
            gather, n=n, d_pad=d_pad, w=mask.shape[2], block_v=block_v)
        if mode == "resident":
            # invalid slots index the plane's guaranteed zero row
            gidx = jnp.where(valid,
                             nbr_c * d_pad + jnp.clip(fwd_rslot, 0),
                             n * d_pad)
            return kops.rrr_expand_step_resident(
                frontier, visited, nbr_c, gidx,
                mask.reshape(n * d_pad, -1), block_v=block_v)
    gmask = jnp.where(valid[:, :, None],
                      mask[nbr_c, jnp.clip(fwd_rslot, 0)],
                      jnp.uint32(0))                       # [n, df, W]
    if kernel:
        return kops.rrr_expand_step(frontier, visited, nbr_c, gmask,
                                    block_v=block_v)
    hit = bitset.or_reduce(frontier[nbr_c] & gmask, axis=1)  # [n, W]
    new = hit & ~visited
    return new, visited | new


# Frontier steps apply their updates this many at a time: a scatter on
# the chip costs about the same for each update it is given, dropped
# ones included, so blocks keep it to the updates a step has.
_SCATTER_BLOCK = 1024


def _frontier_capacity(batch: int) -> Tuple[int, int]:
    """(pairs a frontier step holds, its update block): twice the batch,
    rounded up to whole blocks.  A step with more pairs takes the dense
    step."""
    block = min(_SCATTER_BLOCK, 2 * batch)
    return -(-2 * batch // block) * block, block


def _wide_index(b, stride: int, off):
    """(hi, lo) uint32 words of ``b * stride + off`` for uint32 ``b``
    and ``off`` and a static ``stride`` below 2**32, exact past 2**32
    without 64-bit types: the product is taken in 16-bit limbs."""
    b = b.astype(jnp.uint32)
    b0, b1 = b & 0xFFFF, b >> 16
    s0, s1 = np.uint32(stride & 0xFFFF), np.uint32(stride >> 16)
    p00, p01, p10 = b0 * s0, b0 * s1, b1 * s0
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    lo = (p00 & 0xFFFF) | (mid << 16)
    hi = b1 * s1 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    lo_off = lo + off.astype(jnp.uint32)
    return hi + (lo_off < lo).astype(jnp.uint32), lo_off


def _threefry_words(key):
    """The two uint32 words of a Threefry key, or None for a key of
    another generator."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        if str(jax.random.key_impl(key)) != "threefry2x32":
            return None
        return jax.random.key_data(key)
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return None
    return key


def _uniform_at(key, hi, lo):
    """``jax.random.uniform(key, shape)`` (float32 on [0, 1)) at the
    flat indices ``(hi, lo)`` of ``shape``, under JAX's partitionable
    Threefry: bits ``y1 ^ y2`` of ``threefry2x32(key, (hi, lo))``,
    converted as ``uniform`` converts them."""
    kw = _threefry_words(key)
    y1, y2 = threefry2x32_p.bind(jnp.broadcast_to(kw[0], hi.shape),
                                 jnp.broadcast_to(kw[1], hi.shape), hi, lo)
    bits = ((y1 ^ y2) >> 9) | np.uint32(0x3F800000)
    return lax.bitcast_convert_type(bits, jnp.float32) - np.float32(1.0)


def _first_above(cum, x):
    """For each ``x[i]``, the first index of the nondecreasing ``cum``
    whose value passes it (``searchsorted(cum, x, side="right")``), by
    comparisons against the ends of 128-entry blocks and then within
    one block: no sequential search."""
    blk = 128
    nb = -(-cum.shape[0] // blk)
    cum = jnp.pad(cum, (0, nb * blk - cum.shape[0]),
                  constant_values=cum[-1]).reshape(nb, blk)
    vb = jnp.minimum(jnp.sum(cum[None, :, -1] <= x[:, None], axis=1),
                     nb - 1)
    return vb * blk + jnp.sum(cum[vb] <= x[:, None], axis=1)


def _frontier_pairs(frontier, row_pairs, cap: int):
    """The frontier's (sample, vertex) pairs, at most ``cap`` of them
    (callers ensure it): int32 ``(b, v, on)`` of length ``cap``, pairs
    in vertex order, ``on`` false past the last pair.  Pair i lies in
    the first row whose running pair count passes i, then in the word
    and the bit of that row where the count does."""
    n, w = frontier.shape
    cum = jnp.cumsum(row_pairs)
    i = jnp.arange(cap, dtype=jnp.int32)
    v = jnp.minimum(_first_above(cum, i), n - 1)
    rank = i - (cum[v] - row_pairs[v])
    row = frontier[v]                                   # [cap, W]
    wpc = bitset.popcount(row)
    wcum = jnp.cumsum(wpc, axis=1)
    wi = jnp.minimum(jnp.sum(wcum <= rank[:, None], axis=1), w - 1)
    at = wi[:, None]
    rank = rank - (jnp.take_along_axis(wcum - wpc, at, axis=1)[:, 0])
    word = jnp.take_along_axis(row, at, axis=1)         # [cap, 1]
    bits = (word >> jnp.arange(bitset.WORD_BITS, dtype=jnp.uint32)) & 1
    bcum = jnp.cumsum(bits.astype(jnp.int32), axis=1)
    j = jnp.sum(bcum <= rank[:, None], axis=1)
    return (wi * bitset.WORD_BITS + jnp.minimum(j, bitset.WORD_BITS - 1),
            v, i < cum[-1])


def _frontier_step(frontier, visited, sub, row_pairs, *, nbr_p, prob_p,
                   chunk: int, n_chunks: int, cap: int, block: int):
    """One IC step drawing coins for the frontier's pairs only.

    Pair ``(b, v)`` draws, for each slot ``s`` of ``v``'s reverse row,
    the coin that the dense step draws at ``(b, v, s % chunk)`` of its
    ``uniform(fold_in(sub, s // chunk), (batch, n, chunk))``.  The
    fired ``(nbr[v, s], b)`` are sorted, so that they come first and
    duplicates (two frontier vertices of one sample reaching one
    in-neighbor) sit side by side and drop out; so do already visited
    vertices, and the rest are distinct single bits, which scatter-add
    ORs exactly.  ``new`` is the frontier's array with its pairs'
    words cleared in place.  Clears and hits are applied ``block`` at a
    time, as many blocks as there are pairs and fired slots.  Returns
    (new, visited | new, any new, coins drawn)."""
    n, w = frontier.shape
    b, v, on = _frontier_pairs(frontier, row_pairs, cap)
    off = (v[:, None].astype(jnp.uint32) * np.uint32(chunk)
           + jnp.arange(chunk, dtype=jnp.uint32)[None])
    hi, lo = _wide_index(jnp.broadcast_to(b[:, None], off.shape),
                         n * chunk, off)                # [cap, chunk]
    coins = jnp.concatenate(
        [_uniform_at(jax.random.fold_in(sub, c), hi, lo)
         for c in range(n_chunks)], axis=1)             # [cap, d_pad]
    tgt = nbr_p[v]
    fire = on[:, None] & (tgt >= 0) & (coins < prob_p[v])
    tu, tb = lax.sort((jnp.where(fire, tgt, n).reshape(-1),
                       jnp.broadcast_to(b[:, None], fire.shape).reshape(-1)),
                      num_keys=2)
    first = jnp.ones(tu.shape, bool).at[1:].set(
        (tu[1:] != tu[:-1]) | (tb[1:] != tb[:-1]))

    def blocks(count):
        return (count + block - 1) // block

    def part(k, *arrays):
        return (lax.dynamic_slice(a, (k * block,), (block,)) for a in arrays)

    def clear(k, new):
        cv, cb, c_on = part(k, v, b, on)
        return new.at[jnp.where(c_on, cv, n), cb // bitset.WORD_BITS].set(
            jnp.uint32(0), mode="drop")

    def hit(k, state):
        new, visited, grew = state
        u, sb, f = part(k, tu, tb, first)
        word = sb // bitset.WORD_BITS
        bit = jnp.uint32(1) << (sb % bitset.WORD_BITS).astype(jnp.uint32)
        seen = (visited[jnp.minimum(u, n - 1), word] & bit) != 0
        keep = (u < n) & f & ~seen
        row = jnp.where(keep, u, n)                     # n: dropped
        add = jnp.where(keep, bit, jnp.uint32(0))
        return (new.at[row, word].add(add, mode="drop"),
                visited.at[row, word].add(add, mode="drop"),
                grew | jnp.any(keep))

    pairs = jnp.sum(on, dtype=jnp.int32)
    new = lax.fori_loop(0, blocks(pairs), clear, frontier)   # all zero
    new, visited, grew = lax.fori_loop(
        0, blocks(jnp.sum(fire, dtype=jnp.int32)), hit,
        (new, visited, jnp.bool_(False)))
    drawn = pairs.astype(jnp.float32) * np.float32(coins.shape[1])
    return new, visited, grew, drawn


def _rrr_batch_packed(nbr, prob, wt, fwd_nbr, fwd_rslot, roots, key, *,
                      model: str, max_steps: int, coin_chunk: int,
                      kernel: bool, gather: str = "auto",
                      block_v: Optional[int] = None):
    """The packed BFS engine shared by sampler="packed" and "kernel":
    (visited uint32 [n, W], S1Counts)."""
    n, d = nbr.shape
    batch = roots.shape[0]
    visited0 = _packed_roots(roots, n)
    if d == 0:          # edgeless graph: RRR(root) = {root}
        return visited0, _dense_counts(jnp.int32(0), n, d, batch,
                                       model=model, coin_chunk=coin_chunk)
    valid = nbr >= 0
    chunk, n_chunks, d_pad = _coin_chunks(d, coin_chunk)
    plane = np.float32(coins_per_step(n, d, batch, model=model,
                                      coin_chunk=coin_chunk))

    if model == "IC":
        prob_p = (jnp.pad(prob, ((0, 0), (0, d_pad - d)))
                  if d_pad != d else prob)

        def step_mask(sub):
            # Bit-identical coins to the dense path: same fold_in(sub,
            # c) keys, same [batch, n, chunk] draw shape and order;
            # each chunk packs over the batch lane immediately so the
            # bool slot-mask never exceeds one chunk.
            def one(c, m):
                coins = jax.random.uniform(
                    jax.random.fold_in(sub, c), (batch, n, chunk))
                p_c = lax.dynamic_slice(prob_p, (0, c * chunk),
                                        (n, chunk))
                fire = coins < p_c[None]                # [b, n, chunk]
                pk = _pack_batch_lane(fire, n, chunk, batch)
                return lax.dynamic_update_slice(m, pk, (0, c * chunk, 0))

            mask0 = jnp.zeros((n, d_pad, bitset.num_words(batch)),
                              dtype=bitset.WORD_DTYPE)
            return lax.fori_loop(0, n_chunks, one, mask0)
    else:  # LT live-edge selection mask
        cumw = jnp.cumsum(wt, axis=1)                      # [n, d]
        in_deg = jnp.sum(valid, axis=1)                    # [n]

        def step_mask(sub):
            r = jax.random.uniform(sub, (batch, n))        # same draw
            chosen = jnp.sum(r[:, :, None] >= cumw[None], axis=-1)

            # sel[b, v, slot] = (chosen == slot) & (slot < in_deg[v]):
            # the packed one-hot of the dense path's pick_nbr scatter
            # (slot < in_deg implies nbr[v, slot] >= 0).
            def one(c, m):
                slots = c * chunk + jnp.arange(chunk)
                sel = ((chosen[:, :, None] == slots[None, None]) &
                       (slots[None, None] < in_deg[None, :, None]))
                pk = _pack_batch_lane(sel, n, chunk, batch)
                return lax.dynamic_update_slice(m, pk, (0, c * chunk, 0))

            mask0 = jnp.zeros((n, d_pad, bitset.num_words(batch)),
                              dtype=bitset.WORD_DTYPE)
            return lax.fori_loop(0, n_chunks, one, mask0)

    def dense_step(frontier, visited, sub, _):
        new, visited = _expand_packed(frontier, visited, fwd_nbr,
                                      fwd_rslot, step_mask(sub), kernel,
                                      gather=gather, block_v=block_v)
        return new, visited, jnp.any(new), plane

    # The frontier step needs each coin as a function of its index:
    # IC under a partitionable Threefry key, flat indices within uint32
    # words.
    cap, block = _frontier_capacity(batch)
    if (model == "IC" and _threefry_words(key) is not None
            and jax.config.jax_threefry_partitionable
            and n * chunk < 2 ** 32):
        nbr_p = (jnp.pad(nbr, ((0, 0), (0, d_pad - d)), constant_values=-1)
                 if d_pad != d else nbr)
        sparse_step = functools.partial(
            _frontier_step, nbr_p=nbr_p, prob_p=prob_p, chunk=chunk,
            n_chunks=n_chunks, cap=cap, block=block)

        def step(frontier, visited, sub):
            row_pairs = jnp.sum(bitset.popcount(frontier), axis=1)
            # float32 sums are exact up to 2**24 > cap and never fall
            # back below cap once past it, where int32 could wrap
            fits = jnp.sum(row_pairs, dtype=jnp.float32) <= cap
            out = lax.cond(fits, sparse_step, dense_step, frontier,
                           visited, sub, row_pairs)
            return out + (fits,)
    else:
        def step(frontier, visited, sub):
            return dense_step(frontier, visited, sub, None) + (
                jnp.bool_(False),)

    def body(state):
        frontier, visited, k, live, counts = state
        k, sub = jax.random.split(k)
        new, visited, live, drawn, sparse = step(frontier, visited, sub)
        counts = S1Counts(counts.bfs_steps + 1,
                          counts.frontier_steps + sparse.astype(jnp.int32),
                          counts.coins_drawn + drawn)
        return new, visited, k, live, counts

    def cond(state):
        return state[3] & (state[4].bfs_steps < max_steps)

    zero = S1Counts(jnp.int32(0), jnp.int32(0), jnp.float32(0))
    _, visited, _, _, counts = jax.lax.while_loop(
        cond, body, (visited0, visited0, key, jnp.any(visited0), zero))
    return visited, counts


@functools.partial(
    jax.jit, static_argnames=("model", "max_steps", "coin_chunk", "expand",
                              "gather", "block_v"))
def rrr_batch_packed(nbr, prob, wt, fwd_nbr, fwd_rslot, roots, key, *,
                     model: str, max_steps: int = 64, coin_chunk: int = 32,
                     expand: str = "jax", gather: str = "auto",
                     block_v: Optional[int] = None):
    """Packed-state RRR batch: word-packed incidence [n, W] directly.

    ``(fwd_nbr, fwd_rslot)`` is the padded forward adjacency
    (:func:`repro.graphs.csr.padded_forward_adjacency`).  ``expand``
    picks the expansion engine: "jax" (pure-XLA gather) or "kernel"
    (one fused Pallas launch per BFS step).  Both are bit-identical to
    each other and to ``pack_bool_matrix(rrr_batch(...).T)`` of the
    dense path under the same key/coin_chunk.

    ``gather``/``block_v`` shape the kernel engine only (resident vs
    streamed coin gather, row-tile size — see the module docstring and
    ``kernels.vmem_budget``); neither affects results.

    Returns: uint32 [n, ceil(batch/32)]; bit i of word i//32 at row v
    is set iff v in RRR(roots[i]).
    """
    if expand not in ("jax", "kernel"):
        raise ValueError(f"expand must be 'jax' or 'kernel', got {expand!r}")
    return _rrr_batch_packed(nbr, prob, wt, fwd_nbr, fwd_rslot, roots,
                             key, model=model, max_steps=max_steps,
                             coin_chunk=coin_chunk,
                             kernel=(expand == "kernel"),
                             gather=gather, block_v=block_v)[0]


@functools.partial(jax.jit,
                   static_argnames=("theta", "model", "max_steps", "n",
                                    "sampler", "coin_chunk", "gather",
                                    "block_v"))
def sample_incidence(nbr, prob, wt, key, *, theta: int, n: int,
                     model: str, max_steps: int = 64,
                     sampler: str = "dense", fwd=None,
                     coin_chunk: int = 32, gather: str = "auto",
                     block_v: Optional[int] = None):
    """Sample ``theta`` RRR sets, return packed incidence X [n, W].

    Bit i of X[v] is set iff v is in RRR sample i.  theta must be a
    multiple of 32 (callers round up) so rows pack without straddling.

    ``sampler="packed"|"kernel"`` (requires ``fwd``) runs the BFS on
    word-packed state and emits X *directly* — the dense path's
    [theta, n] bool visited matrix and its pack/transpose epilogue
    never materialize.  All samplers are bit-identical for the same
    key and ``coin_chunk``.
    """
    assert theta % bitset.WORD_BITS == 0
    sampler = resolve_sampler(sampler)
    kr, kb = jax.random.split(key)
    roots = jax.random.randint(kr, (theta,), 0, n)
    if sampler == "dense":
        visited = rrr_batch(nbr, prob, wt, roots, kb, model=model,
                            max_steps=max_steps,
                            coin_chunk=coin_chunk)  # [theta, n]
        return bitset.pack_bool_matrix(visited.T)  # [n, W]
    fwd_nbr, fwd_rslot = _require_fwd(fwd, sampler)
    return rrr_batch_packed(
        nbr, prob, wt, fwd_nbr, fwd_rslot, roots, kb, model=model,
        max_steps=max_steps, coin_chunk=coin_chunk,
        expand=("kernel" if sampler == "kernel" else "jax"),
        gather=gather, block_v=block_v)


def sample_incidence_host(g: CSRGraph, theta: int, key, model: Model = "IC",
                          max_steps: int = 64, batch: int = 256,
                          sampler: str = "dense", coin_chunk: int = 32,
                          gather: str = "auto",
                          block_v: Optional[int] = None):
    """Host-side convenience: batch over theta to bound peak memory.

    ``theta`` is rounded up to a whole number of 32-bit words and the
    returned incidence is trimmed to exactly that many columns — the
    reported theta (second return value) always equals
    ``32 * X.shape[1]``, even when a tail batch was rounded up to pack
    whole words.  The packed samplers build the forward adjacency here
    once and reuse it across batches.
    """
    sampler = resolve_sampler(sampler)
    theta = int(np.ceil(theta / bitset.WORD_BITS) * bitset.WORD_BITS)
    nbr, prob, wt = padded_adjacency(g)
    fwd = (padded_forward_adjacency(g) if sampler != "dense" else None)
    n = g.num_vertices
    chunks = []
    done = 0
    i = 0
    while done < theta:
        b = min(batch, theta - done)
        b = int(np.ceil(b / bitset.WORD_BITS) * bitset.WORD_BITS)
        sub = jax.random.fold_in(key, i)
        chunks.append(sample_incidence(nbr, prob, wt, sub, theta=b, n=n,
                                       model=model, max_steps=max_steps,
                                       sampler=sampler, fwd=fwd,
                                       coin_chunk=coin_chunk,
                                       gather=gather, block_v=block_v))
        done += b
        i += 1
    x = jnp.concatenate(chunks, axis=1)[:, :bitset.num_words(theta)]
    return x, theta  # [n, W], the rounded theta (= 32 * W exactly)
