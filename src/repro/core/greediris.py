"""GreediRIS: the distributed streaming round, SPMD over a JAX mesh.

This is the paper's §3.4 workflow mapped onto TPU-native collectives
(see DESIGN.md §2 for the adaptation table):

  S1 sampling       — shard_map over the machine axes; each shard draws
                      theta/m RRR sets with a fold_in(key, shard) stream
                      (leapfrog analogue: partition-independent RNG).
                      Three sampler paths (`sampler=`), all
                      bit-identical (same key ⇒ identical packed
                      incidence):
                      * "dense":  bool [batch, n] frontier/visited BFS
                        with a scatter expansion, packed + transposed
                        after the fact (the reference path);
                      * "packed": word-packed uint32 [n, batch/32]
                        frontier/visited for the whole BFS (8x fewer
                        state bytes) with a gather expansion over the
                        padded forward adjacency; the packed incidence
                        is emitted directly — no [theta, n] bool
                        intermediate, no pack/transpose epilogue;
                      * "kernel": the packed path with each BFS
                        expansion fused into ONE pallas_call
                        (`kernels.rrr_expand`) — frontier/visited
                        words VMEM-resident, forward-index and packed
                        coin-mask tiles streamed double-buffered.
  S2 all-to-all     — `lax.all_to_all` of the packed incidence bitmatrix
                      (split vertices, concat sample-words) after a
                      globally-agreed random vertex permutation (the
                      RandGreedi uniform partition).
  S3 senders        — vectorized greedy max-k-cover per shard; the first
                      ceil(alpha*k) seed rows form the truncated payload.
                      Four solver paths (`solver=`), all bit-identical:
                      * "scan":     one full gain sweep + argmax per
                        pick (k XLA launches, [n] gain vector and [W]
                        covered mask round-trip HBM every pick);
                      * "fused":    one `best_gain_index` pallas_call
                        per pick (gain sweep + blockwise argmax fused;
                        the gain vector never materializes);
                      * "resident": the whole k-pick greedy loop in ONE
                        pallas_call (`kernels.greedy_pick`) — covered/
                        picked/seeds/gains VMEM-resident throughout,
                        rows double-buffered HBM->VMEM per tile, winner
                        row re-gathered by a single-row DMA;
                      * "lazy":     the resident loop plus tile-level
                        lazy greedy (`kernels.lazy_greedy`) — a
                        [num_tiles] stale-upper-bound vector stays in
                        VMEM and each pick only DMAs + re-sweeps tiles
                        whose bound can still reach the running best
                        (equal bounds re-sweep, keeping the lowest-
                        index tie-break bit-exact).
  S4 receiver       — replicated streaming aggregation.  Two schedules:
                      * "gather":   one all_gather of all payloads, then
                        a streaming pass (2 collective steps total —
                        the paper's headline communication reduction);
                      * "pipeline": an m-step ppermute ring where bucket
                        insertion of chunk r overlaps the permute of
                        chunk r+1 (the SPMD analogue of the paper's
                        nonblocking streaming; also *order-diverse*:
                        each device sees a rotated stream order, and we
                        keep the best bucket solution across devices —
                        a beyond-paper quality bonus at zero extra
                        communication).

Also provides the Ripples-style baseline (`ripples_select_sharded`):
k global psum reductions of an n-sized gain vector — implemented so the
dry-run can *measure* the collective volume GreediRIS eliminates.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import bitset, maxcover, streaming


class GreediRISOut(NamedTuple):
    seeds: jnp.ndarray          # int32 [k] global vertex ids (-1 pad)
    coverage: jnp.ndarray       # int32 [] coverage of returned seeds
    global_coverage: jnp.ndarray   # best streaming-receiver coverage
    best_local_coverage: jnp.ndarray
    # Counters of the round's work, int32 [] but coins_drawn float32 [],
    # summed over sample chunks and machines (denominators:
    # ``RoundUnits``):
    bfs_steps: jnp.ndarray      # S1 BFS steps taken
    rrr_pairs: jnp.ndarray      # S1 (sample, vertex) pairs sampled
    sender_tiles_swept: jnp.ndarray   # S3 row tiles the picks swept
    frontier_steps: jnp.ndarray  # S1 steps that drew for the frontier
    coins_drawn: jnp.ndarray    # S1 uniforms drawn (float32)


class RoundUnits(NamedTuple):
    """Static sizes of a round's work, the denominators of its
    counters: ``build_round(...)[0].units``, or ``round_units`` with
    the same arguments."""
    coins_per_bfs_step: int     # uniforms one S1 BFS step draws
    sender_picks: int           # S3 picks, over all machines
    sender_tiles_per_pick: int  # row tiles one full S3 pick sweeps


def _axis_size(mesh, axes: Sequence[str]) -> int:
    return int(math.prod(mesh.shape[a] for a in axes))


def _round_sizes(n: int, theta: int, m: int):
    """(n_pad, rows per machine, sets per machine) of a round."""
    n_pad = ((n + m - 1) // m) * m
    return n_pad, n_pad // m, ((theta // m + 31) // 32) * 32


def round_units(*, n: int, theta: int, k: int, max_degree: int,
                machines: int, model: str = "IC", sample_chunks: int = 1,
                coin_chunk: int = 32) -> RoundUnits:
    """The ``units`` of ``build_round`` on ``machines`` machines with
    these arguments."""
    from repro.core.rrr import coins_per_step
    _, per, theta_local = _round_sizes(n, theta, machines)
    return RoundUnits(
        coins_per_bfs_step=coins_per_step(
            n, max_degree, theta_local // sample_chunks, model=model,
            coin_chunk=coin_chunk),
        sender_picks=machines * k,
        sender_tiles_per_pick=maxcover.full_sweep_tiles(per, 1))


def build_round(mesh, axes: Sequence[str], *, n: int, theta: int, k: int,
                max_degree: int, model: str = "IC", delta: float = 0.077,
                alpha_trunc: float = 1.0, aggregate: str = "gather",
                max_steps: int = 32, sample_chunks: int = 1,
                use_kernel: bool = False, shuffle: str = "dense",
                est_rrr_len: float = 16.0,
                chunk_size: int | str | None = None,
                solver: str | None = None,
                sampler: str | None = None, fwd=None,
                coin_chunk: int = 32, gather: str = "auto",
                block_v: int | None = None,
                survivors=None):
    """Build the jittable distributed round fn(nbr, prob, wt, key).

    The graph (padded reverse adjacency [n, max_degree]) is replicated
    on every device — the paper's setup ("the input graph is loaded on
    all machines").  Returns a function suitable for jax.jit with the
    given mesh, the padded vertex count and the rounded theta.  The
    function's ``units`` attribute (:class:`RoundUnits`) holds the
    denominators of the counters its :class:`GreediRISOut` carries.

    solver: S3 sender path — "scan" | "fused" | "resident" | "lazy"
    (see the module docstring; all bit-identical).  None defaults from the
    deprecated ``use_kernel`` bool ("fused" when True, "scan"
    otherwise); ``use_kernel`` also still routes the S4 receiver
    through its fused/pipelined kernels.

    chunk_size: receiver insertion granularity under "gather": the
    [m*kk] gathered stream is split into ceil(m*kk / chunk_size)
    chunks (None = whole stream in one chunk, except with use_kernel
    where None means "auto").  With use_kernel the
    whole chunked stream goes through ``streaming.insert_stream`` —
    ONE pipelined pallas_call for the entire stream, covers
    VMEM-resident throughout, chunk r+1's rows double-buffered
    HBM->VMEM while chunk r inserts; without use_kernel each chunk is
    a ``lax.scan`` insertion step (legacy, bit-identical).  The
    string "auto" solves chunk_size from B, W, k and the ~16 MiB VMEM
    budget (``repro.kernels.vmem_budget.receiver_chunk_size``).
    Ignored under "pipeline", whose chunk is inherently the kk-seed
    ring payload (the ppermute of chunk r+1 overlaps the fused
    insertion of chunk r).

    sampler: S1 sampling path — "dense" | "packed" | "kernel" (see the
    module docstring; all bit-identical, so every downstream stage —
    shuffle, senders, receiver — produces identical outputs for the
    same key).  The packed paths need ``fwd=(fwd_nbr, fwd_rslot)``,
    the padded forward adjacency from
    ``repro.graphs.csr.padded_forward_adjacency(g)`` (closed over as a
    replicated constant, like the mesh).

    coin_chunk: IC coin-draw slot width inside the sampler BFS.  It
    bounds the per-step *bool coin intermediate* to
    O(batch * n * coin_chunk) on every sampler; the packed samplers
    additionally hold the word-packed [n, d_max, batch/32] slot mask
    (batch/8 bytes per edge slot — 1/8 of an unchunked bool mask, but
    not bounded by coin_chunk; see ``repro.core.rrr``).  Under IC the
    chunk index is folded into the PRNG stream, so the knob acts like
    a seed — any fixed value keeps the samplers bit-identical to each
    other, changing it changes the sampled sets.

    gather: the kernel sampler's coin-gather layout — "resident" (the
    per-step packed coin-plane stays VMEM-resident, BOTH gathers
    in-kernel, no XLA-side [n, d_out, W] gmask), "streamed" (the
    gmask-stream fallback), or "auto" (VMEM-budget solve; the
    default).  block_v: the expansion kernel's row-tile size (None =
    the ``kernels.vmem_budget`` policy).  Neither affects results —
    ignored by the non-kernel samplers.

    shuffle:
      "dense"  — all_to_all of the packed incidence bitmatrix (paper-
                 faithful fixed-shape adaptation; O(n * theta / 32)
                 bytes regardless of RRR sparsity).  With a packed
                 sampler the bitmatrix comes straight out of S1.
      "sparse" — communication-optimized: exchange (vertex, sample)
                 COO pairs in fixed-capacity per-destination buckets
                 and rebuild the packed rows locally.  Bytes scale
                 with the actual RRR mass (theta * avg_len * 8), a
                 ~2-orders-of-magnitude reduction at production scale
                 (EXPERIMENTS.md §Perf).  ``est_rrr_len`` sizes the
                 buckets (x2 safety); overflow pairs are dropped and
                 counted (quality effect = slightly smaller theta).

    survivors: optional iterable of surviving machine ids — the
    partition-loss-tolerant merge (paper Thm 3.1: the RandGreedi
    guarantee is m-independent, so losing a partition degrades theta,
    not correctness).  Dead machines' sender payloads are masked out
    receiver-side (ids -> -1, rejected unconditionally by the bucket
    insert; rows -> 0) and their local/receiver solutions are excluded
    from the best-of merge, so a lost partition's data cannot reach
    the answer.  None (or all ids) = the unmasked round.  The
    single-controller twin is ``randgreedi_maxcover(survivors=...)``;
    the host-level failure detection that produces this mask lives in
    ``repro.runtime.faults.resilient_randgreedi``.
    """
    if isinstance(chunk_size, str) and chunk_size != "auto":
        raise ValueError(
            f"chunk_size must be an int, None, or 'auto', "
            f"got {chunk_size!r}")
    if isinstance(chunk_size, int) and chunk_size <= 0:
        raise ValueError(
            f"chunk_size must be a positive candidate count, None "
            f"(whole stream), or 'auto', got {chunk_size}")
    if not isinstance(coin_chunk, int) or coin_chunk < 1:
        raise ValueError(
            f"coin_chunk must be a positive slot count (the IC "
            f"coin-draw width; it is part of the PRNG stream, so pick "
            f"one value and keep it), got {coin_chunk!r}")
    if block_v is not None and (not isinstance(block_v, int)
                                or block_v < 1):
        raise ValueError(
            f"block_v must be a positive row-tile size (rounded up to "
            f"a multiple of 8 sublanes) or None for the autotuned/"
            f"analytic policy, got {block_v!r}")
    # use_kernel=False is the bool's default (not "unset"), so only a
    # True value routes through the deprecated-alias path (and warns);
    # it keeps kernelizing the S4 receiver either way.
    solver = maxcover.resolve_solver(solver, use_kernel or None)
    from repro.core.randgreedi import _normalize_survivors
    from repro.core.rrr import (S1Counts, _rrr_batch_dense,
                                _rrr_batch_packed, resolve_sampler)
    from repro.kernels import vmem_budget
    if gather not in vmem_budget.GATHER_MODES:
        # validate eagerly (the knob only binds inside the jitted
        # round, which would surface the error at first trace)
        vmem_budget.resolve_gather(gather, n=1, d_pad=1, w=1)
    sampler = resolve_sampler(sampler)
    if sampler != "dense":
        if fwd is None:
            raise ValueError(
                f"sampler={sampler!r} needs fwd=(fwd_nbr, fwd_rslot) — "
                "pass repro.graphs.csr.padded_forward_adjacency(g)")
        fwd_nbr, fwd_rslot = fwd
        expand = "kernel" if sampler == "kernel" else "jax"
    axes = tuple(axes)
    m = _axis_size(mesh, axes)
    survivors = _normalize_survivors(survivors, m)
    n_pad, per, theta_local = _round_sizes(n, theta, m)
    assert theta_local % sample_chunks == 0 or sample_chunks == 1
    w_local = theta_local // 32
    w_global = (theta_local * m) // 32
    kk = max(1, int(round(alpha_trunc * k)))
    if chunk_size == "auto" or (chunk_size is None and use_kernel
                                and aggregate == "gather"):
        # Solve C from the receiver's VMEM residency: B buckets of
        # W_global words + the double-buffered [2, C, W_global] rows
        # must fit the per-core budget.  This is also the default for
        # the kernelized gather receiver — a single whole-stream chunk
        # would double-buffer the entire m*kk stream in VMEM, which at
        # production scale cannot fit (and buys no overlap at R=1).
        from repro.kernels.vmem_budget import receiver_chunk_size
        chunk_size = receiver_chunk_size(
            streaming.num_buckets(k, delta), w_global, k, total=m * kk)
    # sparse-shuffle bucket capacity: pairs per (src, dst) pair
    cap = max(64, int(2.0 * theta_local * est_rrr_len / m))
    units = round_units(n=n, theta=theta, k=k, max_degree=max_degree,
                        machines=m, model=model,
                        sample_chunks=sample_chunks, coin_chunk=coin_chunk)

    def sample_dense(nbr, prob, wt, roots, kb):
        """One S1 batch under the dense sampler: (bool [b, n], counts)."""
        return _rrr_batch_dense(nbr, prob, wt, roots, kb, model=model,
                                max_steps=max_steps, coin_chunk=coin_chunk)

    def sample_packed(nbr, prob, wt, roots, kb):
        """One S1 batch as (packed words [n, b/32], counts)."""
        if sampler == "dense":
            vis, counts = sample_dense(nbr, prob, wt, roots, kb)
            return bitset.pack_bool_matrix(vis.T), counts  # [n, b/32]
        return _rrr_batch_packed(nbr, prob, wt, fwd_nbr, fwd_rslot,
                                 roots, kb, model=model,
                                 max_steps=max_steps,
                                 coin_chunk=coin_chunk,
                                 kernel=(expand == "kernel"),
                                 gather=gather, block_v=block_v)

    def shard_fn(nbr, prob, wt, key):
        if nbr.shape[1] != max_degree:
            raise ValueError(f"the adjacency has {nbr.shape[1]} slots per "
                             f"vertex; build_round was given max_degree="
                             f"{max_degree}")
        pid = lax.axis_index(axes)
        key_p = jax.random.fold_in(key, pid)
        perm = jax.random.permutation(
            jax.random.fold_in(key, 0x9E37), n_pad)
        inv_perm = jnp.argsort(perm)   # position of vertex v in perm
        no_counts = S1Counts(jnp.int32(0), jnp.int32(0), jnp.float32(0))

        if shuffle == "dense":
            # --- S1: sample theta/m RRR sets, packed bitmatrix ---
            def one_chunk(i, state):
                acc, counts = state
                kc = jax.random.fold_in(key_p, i)
                kr, kb = jax.random.split(kc)
                b = theta_local // sample_chunks
                roots = jax.random.randint(kr, (b,), 0, n)
                packed, st = sample_packed(nbr, prob, wt, roots, kb)
                return (lax.dynamic_update_slice(
                    acc, packed, (0, i * (b // 32))),
                    jax.tree.map(jnp.add, counts, st))

            x_p = jnp.zeros((nbr.shape[0], w_local),
                            dtype=bitset.WORD_DTYPE)
            x_p, s1 = lax.fori_loop(0, sample_chunks, one_chunk,
                                    (x_p, no_counts))
            rrr_pairs = jnp.sum(bitset.popcount(x_p))
            if nbr.shape[0] < n_pad:
                x_p = jnp.pad(x_p, ((0, n_pad - nbr.shape[0]), (0, 0)))
            # --- S2: uniform random partition + dense all-to-all ---
            x_s = lax.all_to_all(x_p[perm], axes, split_axis=0,
                                 concat_axis=1, tiled=True)
        else:
            # --- S1+S2 sparse: COO pair exchange ---
            send = jnp.zeros((m, cap, 2), dtype=jnp.int32)
            counts = jnp.zeros((m,), dtype=jnp.int32)

            def one_chunk(i, state):
                send, counts, s1, rrr_pairs = state
                kc = jax.random.fold_in(key_p, i)
                kr, kb = jax.random.split(kc)
                b = theta_local // sample_chunks
                roots = jax.random.randint(kr, (b,), 0, n)
                size = cap * m // sample_chunks
                if sampler == "dense":
                    vis, st = sample_dense(nbr, prob, wt, roots, kb)
                    s_idx, v_idx = jnp.nonzero(vis, size=size,
                                               fill_value=-1)
                    rrr_pairs = rrr_pairs + jnp.sum(vis, dtype=jnp.int32)
                else:
                    # packed samplers feed the COO exchange through a
                    # word-iterating nonzero — the [b, n] bool matrix
                    # never materializes.
                    packed, st = sample_packed(nbr, prob, wt, roots, kb)
                    s_idx, v_idx = bitset.packed_nonzero(
                        packed, size=size, fill_value=-1)
                    rrr_pairs = rrr_pairs + jnp.sum(
                        bitset.popcount(packed))
                s1 = jax.tree.map(jnp.add, s1, st)
                valid = s_idx >= 0
                sample_gid = pid * theta_local + i * b + s_idx
                pos = inv_perm[jnp.clip(v_idx, 0)]
                dst = jnp.where(valid, pos // per, m)    # m = discard
                onehot = jax.nn.one_hot(dst, m, dtype=jnp.int32)
                rank = jnp.take_along_axis(
                    jnp.cumsum(onehot, axis=0),
                    jnp.clip(dst, 0, m - 1)[:, None], axis=1)[:, 0] - 1
                slot = counts[jnp.clip(dst, 0, m - 1)] + rank
                ok = valid & (slot < cap)
                d_c = jnp.where(ok, dst, m)              # OOB -> drop
                s_c = jnp.where(ok, slot, 0)
                send = send.at[d_c, s_c, 0].set(pos % per, mode="drop")
                send = send.at[d_c, s_c, 1].set(sample_gid, mode="drop")
                counts = counts + jnp.sum(
                    onehot * ok[:, None].astype(jnp.int32), axis=0)
                return send, counts, s1, rrr_pairs

            # mark empty slots with sample id -1
            send = send.at[:, :, 1].set(-1)
            send, counts, s1, rrr_pairs = lax.fori_loop(
                0, sample_chunks, one_chunk,
                (send, counts, no_counts, jnp.int32(0)))
            recv = lax.all_to_all(send, axes, split_axis=0,
                                  concat_axis=0, tiled=True)
            # rebuild packed rows [per, W_global]; each (v, s) pair is
            # a unique bit, so scatter-add == scatter-or.
            flat = recv.reshape(-1, 2)
            v_l, s_g = flat[:, 0], flat[:, 1]
            ok = s_g >= 0
            word = jnp.where(ok, s_g // 32, 0)
            bit = (jnp.where(ok, s_g, 0) % 32).astype(jnp.uint32)
            contrib = jnp.where(ok, jnp.uint32(1) << bit, jnp.uint32(0))
            x_s = jnp.zeros((per, w_global), dtype=bitset.WORD_DTYPE)
            x_s = x_s.at[jnp.where(ok, v_l, 0), word].add(
                contrib, mode="drop")

        # --- S3: local greedy (sender) ---
        sol = maxcover.greedy_maxcover(x_s, k, solver=solver)
        local_ids = jnp.where(
            sol.seeds >= 0, perm[pid * per + jnp.clip(sol.seeds, 0)], -1)
        local_cov = sol.coverage
        gain0 = sol.gains[0].astype(jnp.float32)
        if survivors is not None:
            # Partition-loss-tolerant masking: a dead machine's sender
            # payload is rejected receiver-side (ids -> -1, zero rows)
            # and its local/receiver solutions drop out of the merge,
            # so a lost partition's data cannot reach the answer.
            alive_vec = jnp.zeros((m,), bool).at[
                jnp.asarray(survivors)].set(True)
            alive = alive_vec[pid]
            local_ids = jnp.where(alive, local_ids, -1)
            local_cov = jnp.where(alive, local_cov, -1)
            gain0 = jnp.where(alive, gain0, 0.0)
        sent_ids = local_ids[:kk]
        sent_rows = (sol.rows[:kk] if survivors is None
                     else jnp.where(alive, sol.rows[:kk], 0))

        # l for the bucket thresholds: global max singleton gain
        # (surviving senders only — dead ones contribute nothing).
        lower = lax.pmax(gain0, axes)

        # --- S4: streaming receiver (replicated) ---
        state = streaming.init_state(k, delta, lower, sol.rows.shape[1])
        if aggregate == "gather":
            ids_all = lax.all_gather(sent_ids, axes, tiled=True)   # [m*kk]
            rows_all = lax.all_gather(sent_rows, axes, tiled=True)
            total = m * kk
            if total == 0:
                # Empty candidate stream (statically impossible today —
                # kk >= 1 and m >= 1 — but chunk_stream would otherwise
                # hand the stream kernel an R=0 grid): keep the freshly
                # initialized state, identical to inserting nothing.
                pass
            elif use_kernel:
                # Pipelined receiver: the whole gathered stream in ONE
                # pallas_call — covers VMEM-resident across all
                # chunks, chunk r+1's rows double-buffered HBM->VMEM
                # while chunk r inserts.  Tail padding with id -1
                # (rejected unconditionally, zero rows) is exact.
                cs = min(chunk_size or total, total)
                ids_ch, rows_ch = streaming.chunk_stream(
                    ids_all, rows_all, cs)
                state = streaming.insert_stream(state, ids_ch, rows_ch,
                                                k)
            elif chunk_size and chunk_size < total:
                # Legacy chunked insertion (bit-identical fallback):
                # one scan step per chunk_size candidates.
                ids_ch, rows_ch = streaming.chunk_stream(
                    ids_all, rows_all, chunk_size)

                def chunk_body(st, x):
                    ci, cr = x
                    return streaming.insert_chunk(st, ci, cr, k,
                                                  use_kernel), None

                state, _ = lax.scan(chunk_body, state, (ids_ch, rows_ch))
            else:
                state = streaming.insert_chunk(state, ids_all, rows_all,
                                               k, use_kernel)
        else:  # pipeline: m-step ring; the ppermute of chunk r+1
            # overlaps the (fused, one-launch when use_kernel) bucket
            # insertion of chunk r.
            pairs = [(j, (j + 1) % m) for j in range(m)]

            def ring(carry, _):
                st, b_ids, b_rows = carry
                nxt_ids = lax.ppermute(b_ids, axes, pairs)
                nxt_rows = lax.ppermute(b_rows, axes, pairs)
                # Per-ring-step fused chunk kernel (when use_kernel):
                # the stream kernel's double buffer buys nothing at
                # R=1, so the ring keeps the direct VMEM BlockSpec
                # mapping of its kk-seed payload.
                st = streaming.insert_chunk(st, b_ids, b_rows, k,
                                            use_kernel)
                return (st, nxt_ids, nxt_rows), None

            (state, _, _), _ = lax.scan(
                ring, (state, sent_ids, sent_rows), None, length=m)
        g_seeds, g_cov = streaming.finalize(state)

        # best receiver across devices (identical under "gather";
        # order-diverse under "pipeline" -> keep the best).  Dead
        # machines' receiver copies are excluded like their senders.
        g_cov_all = lax.all_gather(g_cov, axes, tiled=False)       # [m]
        g_seeds_all = lax.all_gather(g_seeds, axes, tiled=False)   # [m, k]
        if survivors is not None:
            g_cov_all = jnp.where(alive_vec, g_cov_all, -1)
        g_best = jnp.argmax(g_cov_all)
        g_cov_best = g_cov_all[g_best]
        g_seeds_best = g_seeds_all[g_best]

        # best local solution (paper Alg. 4 lines 5-6)
        lc_all = lax.all_gather(local_cov, axes, tiled=False)      # [m]
        lids_all = lax.all_gather(local_ids, axes, tiled=False)    # [m, k]
        l_best = jnp.argmax(lc_all)
        take_global = g_cov_best >= lc_all[l_best]
        seeds = jnp.where(take_global, g_seeds_best, lids_all[l_best])
        cov = jnp.maximum(g_cov_best, lc_all[l_best])
        return GreediRISOut(seeds, cov, g_cov_best, lc_all[l_best],
                            lax.psum(s1.bfs_steps, axes),
                            lax.psum(rrr_pairs, axes),
                            lax.psum(sol.tiles_swept, axes),
                            lax.psum(s1.frontier_steps, axes),
                            lax.psum(s1.coins_drawn, axes))

    specs_in = (P(), P(), P(), P())  # graph + key replicated
    specs_out = GreediRISOut(*[P()] * len(GreediRISOut._fields))
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=specs_in,
                       out_specs=specs_out, check_vma=False)
    fn.units = units
    return fn, n_pad, theta_local * m


def build_ripples_round(mesh, axes: Sequence[str], *, n: int, theta: int,
                        k: int, model: str = "IC", max_steps: int = 32,
                        sample_chunks: int = 1, use_kernel: bool = False,
                        unroll_k: bool = False):
    """Baseline: distributed greedy with k global reductions (Ripples
    [12] / DiIMM [14] equivalent — see paper §2.1).  Samples stay
    sharded; every greedy pick all-reduces an n-sized gain vector.

    unroll_k=True unrolls the k-iteration loop so the dry-run's HLO
    parse sees all k all-reduces (cost_analysis does not multiply
    while-loop bodies)."""
    axes = tuple(axes)
    m = _axis_size(mesh, axes)
    theta_local = ((theta // m + 31) // 32) * 32
    w_local = theta_local // 32

    from repro.core.rrr import rrr_batch

    def shard_fn(nbr, prob, wt, key):
        pid = lax.axis_index(axes)
        key_p = jax.random.fold_in(key, pid)

        def one_chunk(i, acc):
            kc = jax.random.fold_in(key_p, i)
            kr, kb = jax.random.split(kc)
            b = theta_local // sample_chunks
            roots = jax.random.randint(kr, (b,), 0, n)
            vis = rrr_batch(nbr, prob, wt, roots, kb, model=model,
                            max_steps=max_steps)
            return lax.dynamic_update_slice(
                acc, bitset.pack_bool_matrix(vis.T), (0, i * (b // 32)))

        x_p = jnp.zeros((n, w_local), dtype=bitset.WORD_DTYPE)
        x_p = lax.fori_loop(0, sample_chunks, one_chunk, x_p)

        def body(i, state):
            covered, seeds, picked = state
            if use_kernel:
                from repro.kernels import ops as kops
                gains = kops.marginal_gain(x_p, covered)
            else:
                gains = bitset.marginal_gain(x_p, covered)
            total = lax.psum(gains, axes)   # the k-th O(n) all-reduce
            total = jnp.where(picked, -1, total)
            best = jnp.argmax(total)
            take = total[best] > 0
            covered = covered | jnp.where(take, x_p[best],
                                          jnp.zeros_like(covered))
            seeds = seeds.at[i].set(
                jnp.where(take, best.astype(jnp.int32), -1))
            picked = picked.at[best].set(take | picked[best])
            return covered, seeds, picked

        covered = jnp.zeros((w_local,), dtype=bitset.WORD_DTYPE)
        seeds = jnp.full((k,), -1, dtype=jnp.int32)
        picked = jnp.zeros((n,), dtype=bool)
        if unroll_k:
            state = (covered, seeds, picked)
            for i in range(k):
                state = body(i, state)
            covered, seeds, picked = state
        else:
            covered, seeds, picked = lax.fori_loop(
                0, k, body, (covered, seeds, picked))
        cov = lax.psum(bitset.coverage_size(covered), axes)
        return seeds, cov

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(), P(), P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    return fn, theta_local * m
