"""Online influence service: generation-tagged resident sketch pool +
batched query serving.

The one-shot GreediRIS pipeline amortizes one expensive RIS sample set
across a single max-k-cover solve.  This module inverts that for the
millions-of-users scenario: the packed ``uint32 [n, W]`` RRR incidence
stays *resident* as a sketch pool (two OPIM halves — R1 for selection,
R2 for validation) and MANY concurrent ``(k, seed-constraint, budget)``
queries are answered against the same pool with ONE vmapped solve over
the sender quad — the row stream is shared (``in_axes=None``) while
only the tiny per-query state (covered words + k seed slots + E
exclusion slots) fans out, following the sketch-sharing design of
Cohen et al. (arXiv:1408.6282).

Pool lifecycle
--------------
  * The pool samples in fixed *slabs* of ``slab`` RRR sets (whole
    32-bit words).  Slab ``s`` of half ``h`` is keyed
    ``fold_in(fold_in(fold_in(key, h), s), salt[s])`` where ``salt[s]``
    is the generation that (re)sampled the slab — so growth appends
    slabs without touching existing columns (bit-identical prefix) and
    mutation resamples only affected slabs.
  * ``refresh`` grows theta (default: double, capped at ``max_theta``)
    — the error-adaptive theta schedule of count-distinct sampling
    (arXiv:2105.04023): the pool stays as small as the live queries'
    certificates allow and only grows when one fails.
  * ``refresh_mutated`` applies a graph mutation *incrementally*: an
    RRR set that contains none of the mutated edge heads never crossed
    a changed in-edge list, so its reverse traversal is identical on
    the new graph — only slabs whose samples touch a mutated head are
    resampled (on the new graph, with a fresh generation salt);
    everything else is carried over column-for-column.
  * Every refresh bumps the pool ``generation``.  Queries are admitted
    against a generation (``Ticket``); after a refresh, in-flight
    tickets *drain* on their old generation's pool (kept until
    drained), while answering a ticket whose generation has been
    retired raises :class:`StaleGenerationError`.

Admission rule
--------------
A query is *certified* when the OPIM instance-wise certificate
(``repro.core.opim.certify``: sigma_lower from R2 concentration /
sigma_upper on OPT from R1 greedy coverage) reaches
``alpha - query.eps``, or when the query carries a spread budget and
``sigma_lower`` already clears it.  :meth:`InfluenceService.serve`
re-admits uncertified queries against a refreshed (theta-doubled)
generation until certified or ``max_theta`` is reached — the OPIM-C
doubling loop, amortized across the whole pool instead of re-run per
query.
"""
from __future__ import annotations

import math
import time
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset, maxcover, opim
from repro.core.cascade import MODELS as _MODELS
from repro.graphs.csr import (CSRGraph, padded_adjacency,
                              padded_forward_adjacency)
from repro.core.rrr import SAMPLERS as _SAMPLERS
from repro.core.rrr import resolve_sampler, sample_incidence
from repro.runtime.faults import (FaultPlan, InjectedFault,
                                  fire as _fire_fault)
from repro.runtime.spans import span


# Static contract (proved by repro.analysis on a canonical fixture):
# B concurrent seed-constrained queries batch into ONE vmapped launch
# whose grid carries the batch axis — the sketch pool itself is shared
# (in_axes=None), so the launch count must not scale with B.
CONTRACT = dict(
    family="service",
    launches=1,
    in_loop=False,
    dtypes=("bool", "int32", "uint32"),
    aliases=(),
)


class EmptyPoolError(RuntimeError):
    """Raised when answering against a pool that holds no samples."""


class StaleGenerationError(RuntimeError):
    """Raised when a ticket's generation has been retired."""


class Query(NamedTuple):
    """One influence query.

    k:        max seeds to select (>= 1).
    excluded: vertex ids forbidden as seeds (seed-constraint — e.g.
              vertices already seeded by an earlier campaign).
    budget:   target expected spread (vertices); selection stops at the
              first seed whose running sketch estimate reaches it.
              ``None`` = no budget (select k seeds).
    eps:      admission slack — the answer is certified when the OPIM
              guarantee reaches ``alpha - eps``.
    """
    k: int
    excluded: Tuple[int, ...] = ()
    budget: Optional[float] = None
    eps: float = 0.3


class Ticket(NamedTuple):
    """Admission receipt: the query plus the pool generation it will be
    answered against (the generation tag)."""
    generation: int
    query: Query


class Answer(NamedTuple):
    seeds: np.ndarray       # int32 [query.k]; -1 pads past k_used
    k_used: int             # seeds actually selected (budget/exhaustion)
    coverage: int           # R1 coverage of the selected seeds
    spread: float           # sketch estimate: coverage * n / theta
    sigma_lower: float      # certified lower bound on sigma(S)   (R2)
    sigma_upper: float      # certified upper bound on sigma(OPT) (R1)
    guarantee: float        # sigma_lower / sigma_upper
    certified: bool         # admission rule satisfied at this theta
    generation: int         # pool generation that answered
    degraded: bool = False  # serve() gave up (deadline / max_theta)
    #   before certification — the answer still carries its honest
    #   ``opim.certify`` lower bound (sigma_lower / guarantee above).


class SketchPool(NamedTuple):
    """Generation-tagged resident sketch pool (two OPIM halves).

    ``r1``/``r2`` are packed incidences ``uint32 [n, W]`` with
    ``theta = 32 * W`` samples each; ``salt`` is int32 [num_slabs] —
    the generation that sampled each slab (the PRNG salt that makes
    incremental growth/mutation deterministic and testable).
    """
    g: CSRGraph
    r1: jnp.ndarray
    r2: jnp.ndarray
    theta: int
    generation: int
    salt: np.ndarray
    key: jax.Array
    slab: int
    model: str
    sampler: str
    coin_chunk: int
    max_steps: int

    @property
    def n(self) -> int:
        return self.g.num_vertices

    @property
    def words(self) -> int:
        return bitset.num_words(self.theta)


def _round_to_slabs(theta: int, slab: int) -> int:
    return int(math.ceil(theta / slab)) * slab if theta > 0 else 0


def _sample_slabs(g: CSRGraph, key, slabs: Sequence[Tuple[int, int]],
                  *, slab: int, model: str, sampler: str,
                  coin_chunk: int, max_steps: int,
                  plan: Optional[FaultPlan] = None):
    """Sample [n, slab/32] incidence blocks for each (slab_index, salt)
    of both halves.  Returns (blocks1, blocks2) lists aligned with
    ``slabs``.  Each slab fill is a ``sampler.slab_fill`` injection
    site of ``plan`` — the fill is a pure function of (key, slab,
    salt), so an injected raise aborted pool build can simply be
    retried."""
    n = g.num_vertices
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g) if sampler != "dense" else None
    out = ([], [])
    for half in (0, 1):
        kh = jax.random.fold_in(key, half)
        for (s, salt) in slabs:
            _fire_fault(plan, "sampler.slab_fill", half=half, slab=s,
                        salt=salt)
            ks = jax.random.fold_in(jax.random.fold_in(kh, s), salt)
            out[half].append(sample_incidence(
                nbr, prob, wt, ks, theta=slab, n=n, model=model,
                max_steps=max_steps, sampler=sampler, fwd=fwd,
                coin_chunk=coin_chunk))
    return out


def make_pool(g: CSRGraph, key, *, theta: int = 0, slab: int = 256,
              model: str = "IC", sampler: str = "dense",
              coin_chunk: int = 32, max_steps: int = 32,
              plan: Optional[FaultPlan] = None) -> SketchPool:
    """Create a pool with ``theta`` samples per half (rounded up to
    whole slabs; 0 = empty pool — the first ``refresh`` fills it)."""
    if slab % bitset.WORD_BITS != 0 or slab < bitset.WORD_BITS:
        raise ValueError(f"slab must be a positive multiple of "
                         f"{bitset.WORD_BITS}, got {slab}")
    resolve_sampler(sampler)
    theta = _round_to_slabs(theta, slab)
    num_slabs = theta // slab
    n = g.num_vertices
    w = bitset.num_words(theta)
    if num_slabs == 0:
        empty = jnp.zeros((n, 0), dtype=bitset.WORD_DTYPE)
        return SketchPool(g, empty, empty, 0, 0,
                          np.zeros((0,), np.int32), key, slab, model,
                          sampler, coin_chunk, max_steps)
    blocks1, blocks2 = _sample_slabs(
        g, key, [(s, 0) for s in range(num_slabs)], slab=slab,
        model=model, sampler=sampler, coin_chunk=coin_chunk,
        max_steps=max_steps, plan=plan)
    r1 = jnp.concatenate(blocks1, axis=1)[:, :w]
    r2 = jnp.concatenate(blocks2, axis=1)[:, :w]
    return SketchPool(g, r1, r2, theta, 0,
                      np.zeros((num_slabs,), np.int32), key, slab,
                      model, sampler, coin_chunk, max_steps)


def refresh(pool: SketchPool, new_theta: Optional[int] = None,
            *, max_theta: int = 1 << 20,
            plan: Optional[FaultPlan] = None) -> SketchPool:
    """Grow the pool to ``new_theta`` samples per half (default:
    double, at least one slab), appending new slabs salted with the new
    generation — existing columns are carried over bit-identically.
    Returns a NEW pool with ``generation + 1``; the old pool object
    stays valid so in-flight queries can drain on their tag."""
    if new_theta is None:
        new_theta = max(pool.theta * 2, pool.slab)
    new_theta = min(_round_to_slabs(new_theta, pool.slab), max_theta)
    if new_theta <= pool.theta:
        raise ValueError(
            f"refresh must grow the pool: theta {pool.theta} -> "
            f"{new_theta} (max_theta {max_theta})")
    gen = pool.generation + 1
    old_slabs = pool.theta // pool.slab
    num_slabs = new_theta // pool.slab
    blocks1, blocks2 = _sample_slabs(
        pool.g, pool.key, [(s, gen) for s in range(old_slabs, num_slabs)],
        slab=pool.slab, model=pool.model, sampler=pool.sampler,
        coin_chunk=pool.coin_chunk, max_steps=pool.max_steps, plan=plan)
    r1 = jnp.concatenate([pool.r1] + blocks1, axis=1)
    r2 = jnp.concatenate([pool.r2] + blocks2, axis=1)
    salt = np.concatenate([pool.salt,
                           np.full((num_slabs - old_slabs,), gen,
                                   np.int32)])
    return pool._replace(r1=r1, r2=r2, theta=new_theta, generation=gen,
                         salt=salt)


def affected_slabs(pool: SketchPool, touched) -> np.ndarray:
    """Slab indices whose samples contain a touched vertex (in either
    half) — the conservative invalidation set of a graph mutation.

    A reverse-BFS sample that never reached vertex ``v`` never examined
    ``v``'s in-edge list, so changing that list cannot change the
    sample; only samples *containing* some touched head can differ on
    the mutated graph."""
    touched = np.asarray(list(touched), dtype=np.int64)
    if touched.size == 0 or pool.theta == 0:
        return np.zeros((0,), np.int64)
    hit = (np.asarray(pool.r1)[touched] | np.asarray(pool.r2)[touched])
    words_hit = hit.any(axis=0)                      # [W] word mask
    words_per_slab = pool.slab // bitset.WORD_BITS
    per_slab = words_hit.reshape(-1, words_per_slab).any(axis=1)
    return np.nonzero(per_slab)[0]


def refresh_mutated(pool: SketchPool, g_new: CSRGraph, touched,
                    *, plan: Optional[FaultPlan] = None) -> SketchPool:
    """Apply a graph mutation incrementally: resample only the slabs
    whose samples contain a ``touched`` vertex (an in-edge-list head
    of an inserted/deleted/re-weighted edge), on the NEW graph with a
    fresh generation salt; every other column is carried over
    bit-identically.  Returns a NEW pool with ``generation + 1``."""
    if g_new.num_vertices != pool.n:
        raise ValueError("mutation must preserve the vertex set "
                         f"({pool.n} != {g_new.num_vertices})")
    gen = pool.generation + 1
    stale = affected_slabs(pool, touched)
    if pool.theta == 0 or stale.size == 0:
        return pool._replace(g=g_new, generation=gen)
    blocks1, blocks2 = _sample_slabs(
        g_new, pool.key, [(int(s), gen) for s in stale], slab=pool.slab,
        model=pool.model, sampler=pool.sampler,
        coin_chunk=pool.coin_chunk, max_steps=pool.max_steps, plan=plan)
    wps = pool.slab // bitset.WORD_BITS
    r1, r2 = np.asarray(pool.r1).copy(), np.asarray(pool.r2).copy()
    salt = pool.salt.copy()
    for i, s in enumerate(stale):
        r1[:, s * wps:(s + 1) * wps] = np.asarray(blocks1[i])
        r2[:, s * wps:(s + 1) * wps] = np.asarray(blocks2[i])
        salt[s] = gen
    return pool._replace(g=g_new, r1=jnp.asarray(r1), r2=jnp.asarray(r2),
                         generation=gen, salt=salt)


# ---------------------------------------------------------------------
# Pool snapshot / restore (service recovery via checkpoint.store)
# ---------------------------------------------------------------------

# The static pool fields are encoded as small-int codes in a fixed
# int64 scalars leaf so the snapshot tree has a FIXED structure
# (CheckpointStore.restore matches leaf-for-leaf against a template):
#   [theta, generation, slab, coin_chunk, max_steps,
#    model_code, sampler_code, typed_key_flag]
_POOL_SCALARS = 8


def pool_state(pool: SketchPool) -> dict:
    """The checkpointable state of a pool: 5 array leaves (key data,
    both OPIM halves, slab salts, static scalars).  The graph is NOT
    included — it is the service's configuration, supplied again at
    :func:`pool_from_state` time.  ``pool_from_state(g, pool_state(p))``
    reconstructs ``p`` bit-for-bit (same samples, same salts, same
    PRNG key for future refreshes)."""
    key = pool.key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key_data, typed = jax.random.key_data(key), 1
    else:
        key_data, typed = key, 0  # legacy uint32 [2] PRNGKey
    try:
        model_code = _MODELS.index(pool.model)
        sampler_code = _SAMPLERS.index(pool.sampler)
    except ValueError:
        raise ValueError(
            f"cannot snapshot pool with model={pool.model!r} / "
            f"sampler={pool.sampler!r}; known models {_MODELS}, "
            f"samplers {_SAMPLERS}") from None
    scalars = np.asarray(
        [pool.theta, pool.generation, pool.slab, pool.coin_chunk,
         pool.max_steps, model_code, sampler_code, typed], np.int64)
    return {
        "key": np.asarray(key_data, np.uint32),
        "r1": pool.r1,
        "r2": pool.r2,
        "salt": np.asarray(pool.salt, np.int32),
        "scalars": scalars,
    }


def pool_template(g: CSRGraph) -> dict:
    """A structural template for :meth:`CheckpointStore.restore` —
    shapes/dtypes are placeholders (restore only matches the tree
    structure; real shapes come from the checkpoint files)."""
    del g  # structure is graph-independent; kept for call symmetry
    z = np.zeros((0,), np.uint32)
    return {"key": z, "r1": z, "r2": z,
            "salt": np.zeros((0,), np.int32),
            "scalars": np.zeros((_POOL_SCALARS,), np.int64)}


def pool_from_state(g: CSRGraph, state: dict) -> SketchPool:
    """Rebuild a :class:`SketchPool` from :func:`pool_state` output
    (possibly round-tripped through a :class:`CheckpointStore`)."""
    sc = [int(x) for x in np.asarray(state["scalars"]).reshape(-1)]
    if len(sc) != _POOL_SCALARS:
        raise ValueError(f"pool snapshot scalars must have "
                         f"{_POOL_SCALARS} entries, got {len(sc)}")
    (theta, gen, slab, coin_chunk, max_steps,
     model_code, sampler_code, typed) = sc
    key = jnp.asarray(np.asarray(state["key"]).astype(np.uint32))
    if typed:
        key = jax.random.wrap_key_data(key)
    n, w = g.num_vertices, bitset.num_words(theta)
    r1 = jnp.asarray(state["r1"], bitset.WORD_DTYPE).reshape(n, w)
    r2 = jnp.asarray(state["r2"], bitset.WORD_DTYPE).reshape(n, w)
    salt = np.asarray(state["salt"], np.int32).reshape(
        theta // slab if theta else 0)
    return SketchPool(g, r1, r2, theta, gen, salt, key, slab,
                      _MODELS[model_code], _SAMPLERS[sampler_code],
                      coin_chunk, max_steps)


def snapshot_pool(store, pool: SketchPool, *, step: Optional[int] = None,
                  blocking: bool = True) -> int:
    """Write the pool to a :class:`~repro.checkpoint.store.CheckpointStore`
    (default step = the pool generation) and return the step written.
    Blocking by default: a recovery snapshot that silently failed is
    worse than a slow one."""
    step = pool.generation if step is None else step
    store.save(step, pool_state(pool), blocking=blocking)
    return step


def restore_pool(store, g: CSRGraph, *,
                 step: Optional[int] = None):
    """Load the newest (or requested) pool snapshot.  Returns
    ``(pool, step)`` or ``(None, -1)`` when the store is empty."""
    tree, got = store.restore(pool_template(g), step=step)
    if tree is None:
        return None, -1
    return pool_from_state(g, tree), got


# ---------------------------------------------------------------------
# Batched query engine
# ---------------------------------------------------------------------

def per_query_state_bytes(words: int, k: int, excl: int) -> int:
    """VMEM-resident per-query solve state: covered words + k seed and
    gain slots + E exclusion slots.  The [n, W] row pool is SHARED
    across the batch (amortized, not per-query) — this is the number
    the batched engine fans out per concurrent query."""
    return 4 * words + 4 * k + 4 * k + 4 * excl


def _query_arrays(queries: Sequence[Query], n: int, theta: int):
    """(k_max, excl [B, E], ks [B], budget_cov [B]) of a batch."""
    if not queries:
        raise ValueError("empty query batch")
    for q in queries:
        if q.k < 1:
            raise ValueError(f"query k must be >= 1, got {q.k}")
        for v in q.excluded:
            if not (0 <= int(v) < n):
                raise ValueError(f"excluded id {v} out of range [0, {n})")
    k_max = max(q.k for q in queries)
    e_max = max(1, max(len(q.excluded) for q in queries))
    excl = np.full((len(queries), e_max), -1, np.int32)
    for b, q in enumerate(queries):
        if q.excluded:
            excl[b, :len(q.excluded)] = np.asarray(q.excluded, np.int32)
    ks = np.asarray([q.k for q in queries], np.int32)
    # Budget in coverage units: the smallest R1 coverage whose sketch
    # estimate (cov * n / theta) reaches the requested spread.
    budget_cov = np.asarray(
        [np.iinfo(np.int32).max if q.budget is None
         else int(math.ceil(q.budget * theta / n)) for q in queries],
        np.int32)
    return k_max, excl, ks, budget_cov


def _truncate_one(seeds, sel_rows, gains, kq, budget_cov, r2):
    """Per-query epilogue: budget/k truncation + R2 validation.

    Greedy picks are prefix-consistent, so truncating a k_max solve at
    ``kq`` (or at the first pick whose cumulative coverage reaches the
    budget) is bit-identical to solving with that k directly.
    """
    k = seeds.shape[0]
    csum = jnp.cumsum(gains)
    reached = csum >= budget_cov
    jstar = jnp.where(jnp.any(reached), jnp.argmax(reached) + 1, kq)
    jstar = jnp.minimum(jstar, kq)
    use = jnp.arange(k) < jstar
    seeds_t = jnp.where(use, seeds, -1)
    gains_t = jnp.where(use, gains, 0)
    covered1 = bitset.or_reduce(
        jnp.where(use[:, None], sel_rows, 0), axis=0)
    cov1 = bitset.coverage_size(covered1)
    valid = seeds_t >= 0
    rows2 = r2[jnp.where(valid, seeds_t, 0)]
    covered2 = bitset.or_reduce(
        jnp.where(valid[:, None], rows2, 0), axis=0)
    cov2 = bitset.coverage_size(covered2)
    return seeds_t, gains_t, cov1, cov2, jnp.sum(valid.astype(jnp.int32))


@jax.jit
def _finalize_batch(seeds, sel_rows, gains, ks, budget_cov, r2):
    return jax.vmap(_truncate_one,
                    in_axes=(0, 0, 0, 0, 0, None))(
        seeds, sel_rows, gains, ks, budget_cov, r2)


def _answers(pool: SketchPool, queries: Sequence[Query], seeds_t,
             cov1, cov2, k_used, *, delta: float,
             alpha: float) -> list[Answer]:
    out = []
    for b, q in enumerate(queries):
        c1, c2 = float(cov1[b]), float(cov2[b])
        sig_l, sig_u, guar = opim.certify(c1, c2, pool.theta, pool.n,
                                          delta, alpha)
        spread = c1 * pool.n / pool.theta
        certified = guar >= alpha - q.eps or (
            q.budget is not None and sig_l >= q.budget)
        out.append(Answer(
            seeds=np.asarray(seeds_t[b])[:q.k], k_used=int(k_used[b]),
            coverage=int(cov1[b]), spread=spread, sigma_lower=sig_l,
            sigma_upper=sig_u, guarantee=guar, certified=bool(certified),
            generation=pool.generation))
    return out


def answer_batch(pool: SketchPool, queries: Sequence[Query], *,
                 solver: str = "resident", delta: float = 1.0 / 128.0,
                 alpha: Optional[float] = None) -> list[Answer]:
    """Answer B concurrent queries with ONE vmapped solve over the
    shared R1 pool (plus one vmapped truncation/validation epilogue).

    Bit-identical per query to :func:`answer_one` for every solver in
    the quad: the batch solves every query at ``k_max = max(k)`` and
    truncates — greedy prefix-consistency makes that exact — while the
    [n, W] row stream is shared across the batch (``in_axes=None``)
    and only the O(W + k + E) per-query state fans out
    (:func:`per_query_state_bytes`).
    """
    if pool.theta == 0:
        raise EmptyPoolError(
            "sketch pool holds no samples; refresh it before answering "
            "(InfluenceService.admit does this automatically)")
    if alpha is None:
        alpha = 1.0 - 1.0 / math.e
    with span("service.prepare"):
        k_max, excl, ks, budget_cov = _query_arrays(queries, pool.n,
                                                    pool.theta)
        excl, ks, budget_cov = (jnp.asarray(a)
                                for a in (excl, ks, budget_cov))
    with span("service.dispatch"):
        sol = maxcover.greedy_maxcover_batch(pool.r1, excl, k_max,
                                             solver=solver)
        out = _finalize_batch(sol.seeds, sol.rows, sol.gains, ks,
                              budget_cov, pool.r2)
    with span("service.wait"):
        seeds_t, _, cov1, cov2, k_used = jax.block_until_ready(out)
    with span("service.answers"):
        return _answers(pool, queries, seeds_t, cov1, cov2, k_used,
                        delta=delta, alpha=alpha)


def answer_one(pool: SketchPool, query: Query, *,
               solver: str = "resident", delta: float = 1.0 / 128.0,
               alpha: Optional[float] = None) -> Answer:
    """Sequential per-query reference: one un-batched solve at the
    query's own k.  The serve smoke test and the CI gate hold
    :func:`answer_batch` bit-identical to this path."""
    if pool.theta == 0:
        raise EmptyPoolError("sketch pool holds no samples")
    if alpha is None:
        alpha = 1.0 - 1.0 / math.e
    _, excl, ks, budget_cov = _query_arrays([query], pool.n, pool.theta)
    sol = maxcover.greedy_maxcover(pool.r1, query.k, solver=solver,
                                   excluded=jnp.asarray(excl[0]))
    seeds_t, _, cov1, cov2, k_used = jax.jit(_truncate_one)(
        sol.seeds, sol.rows, sol.gains, jnp.int32(ks[0]),
        jnp.int32(budget_cov[0]), pool.r2)
    return _answers(pool, [query], seeds_t[None], cov1[None], cov2[None],
                    k_used[None], delta=delta, alpha=alpha)[0]


def estimate_spread(pool: SketchPool, seeds) -> float:
    """Sketch-based spread estimate of an explicit seed set against
    the validation half (Cohen-style cheap per-query estimate: one
    gather + popcount, no simulation)."""
    if pool.theta == 0:
        raise EmptyPoolError("sketch pool holds no samples")
    seeds = np.asarray(seeds)
    seeds = seeds[seeds >= 0]
    cov = maxcover.coverage_of(np.asarray(pool.r2), seeds)
    return float(cov) * pool.n / pool.theta


# ---------------------------------------------------------------------
# Service front-end: admission, generation drain, adaptive refresh
# ---------------------------------------------------------------------

class InfluenceService:
    """Serving front-end over a :class:`SketchPool`.

    Holds the current pool plus any draining predecessors (old
    generations with in-flight tickets).  ``admit`` tags a query with
    the current generation; ``answer`` batches tickets per generation
    and retires drained pools; ``serve`` is the full admission loop
    (answer, refresh-on-uncertified, re-answer).
    """

    def __init__(self, g: CSRGraph, key, *, theta0: int = 512,
                 max_theta: int = 1 << 14, slab: int = 256,
                 solver: str = "resident", model: str = "IC",
                 sampler: str = "dense", coin_chunk: int = 32,
                 max_steps: int = 32, delta: float = 1.0 / 128.0,
                 alpha: Optional[float] = None,
                 fault_plan: Optional[FaultPlan] = None):
        self._configure(solver=solver, theta0=theta0,
                        max_theta=max_theta, slab=slab, delta=delta,
                        alpha=alpha, fault_plan=fault_plan)
        pool = make_pool(g, key, theta=0, slab=slab, model=model,
                         sampler=sampler, coin_chunk=coin_chunk,
                         max_steps=max_steps, plan=fault_plan)
        self._pools: dict[int, SketchPool] = {0: pool}
        self._inflight: dict[int, int] = {0: 0}
        self._gen = 0

    def _configure(self, *, solver, theta0, max_theta, slab, delta,
                   alpha, fault_plan):
        maxcover.resolve_solver(solver)
        self.solver = solver
        self.theta0 = _round_to_slabs(max(theta0, slab), slab)
        self.max_theta = _round_to_slabs(max_theta, slab)
        self.delta = delta
        self.alpha = alpha if alpha is not None else 1.0 - 1.0 / math.e
        self.fault_plan = fault_plan

    @classmethod
    def from_pool(cls, pool: SketchPool, *, theta0: int = 512,
                  max_theta: int = 1 << 14, solver: str = "resident",
                  delta: float = 1.0 / 128.0,
                  alpha: Optional[float] = None,
                  fault_plan: Optional[FaultPlan] = None
                  ) -> "InfluenceService":
        """Rebuild a service around a restored pool (see
        :func:`restore_pool`) — the recovery path of the supervised
        serve replay.  The service resumes at the pool's generation;
        future refreshes continue the same salted-slab PRNG stream, so
        a recovered service is bit-identical to one that never died."""
        svc = cls.__new__(cls)
        svc._configure(solver=solver, theta0=theta0,
                       max_theta=max_theta, slab=pool.slab, delta=delta,
                       alpha=alpha, fault_plan=fault_plan)
        svc._pools = {pool.generation: pool}
        svc._inflight = {pool.generation: 0}
        svc._gen = pool.generation
        return svc

    @property
    def generation(self) -> int:
        return self._gen

    @property
    def pool(self) -> SketchPool:
        return self._pools[self._gen]

    def inflight(self, generation: Optional[int] = None) -> int:
        gen = self._gen if generation is None else generation
        return self._inflight.get(gen, 0)

    # -- lifecycle ----------------------------------------------------

    def _install(self, pool: SketchPool):
        self._pools[pool.generation] = pool
        self._inflight.setdefault(pool.generation, 0)
        self._gen = pool.generation
        self._retire_drained()

    def _retire_drained(self):
        for gen in [g for g in self._pools
                    if g != self._gen and self._inflight.get(g, 0) == 0]:
            del self._pools[gen]
            self._inflight.pop(gen, None)

    def refresh(self, new_theta: Optional[int] = None):
        """Grow theta (default: double, first fill = theta0) under a
        new generation tag; drained old generations are retired, ones
        with in-flight tickets are kept for draining."""
        pool = self.pool
        if new_theta is None:
            new_theta = self.theta0 if pool.theta == 0 else min(
                pool.theta * 2, self.max_theta)
        self._install(refresh(pool, new_theta, max_theta=self.max_theta,
                              plan=self.fault_plan))

    def mutate(self, g_new: CSRGraph, touched):
        """Incremental refresh after a graph mutation (``touched`` =
        heads of inserted/deleted/re-weighted edges)."""
        self._install(refresh_mutated(self.pool, g_new, touched,
                                      plan=self.fault_plan))

    # -- admission / answering ---------------------------------------

    def admit(self, query: Query) -> Ticket:
        """Validate and tag a query with the current generation.  An
        empty pool triggers the initial fill (theta0) first — the
        empty-pool admission path."""
        if query.k < 1 or query.k > self.pool.n:
            raise ValueError(f"query k must be in [1, {self.pool.n}], "
                             f"got {query.k}")
        if query.budget is not None and query.budget > self.pool.n:
            raise ValueError(f"budget {query.budget} exceeds the vertex "
                             f"count {self.pool.n}")
        _fire_fault(self.fault_plan, "service.admit", k=query.k,
                    generation=self._gen)
        if self.pool.theta == 0:
            self.refresh()
        self._inflight[self._gen] += 1
        return Ticket(self._gen, query)

    def release(self, tickets: Sequence[Ticket]):
        """Abandon admitted tickets without answering them (the
        retry path re-admits on the current generation) so their old
        generations can drain and retire."""
        for t in tickets:
            if t.generation in self._inflight:
                self._inflight[t.generation] = max(
                    0, self._inflight[t.generation] - 1)
        self._retire_drained()

    def answer(self, tickets: Sequence[Ticket]) -> list[Answer]:
        """Answer a batch of tickets; tickets sharing a generation are
        answered by one vmapped solve against that generation's pool
        (stale generations raise, draining ones complete).  Returns
        answers in ticket order.

        Both failure modes raise BEFORE any in-flight count is
        consumed, so the batch can be retried/re-admitted whole (see
        :func:`answer_with_retry`)."""
        _fire_fault(self.fault_plan, "service.answer",
                    batch=len(tickets))
        for t in tickets:
            if t.generation not in self._pools:
                raise StaleGenerationError(
                    f"generation {t.generation} has been retired "
                    f"(current: {self._gen})")
        by_gen: dict[int, list[int]] = {}
        for i, t in enumerate(tickets):
            by_gen.setdefault(t.generation, []).append(i)
        out: list[Optional[Answer]] = [None] * len(tickets)
        for gen, idxs in by_gen.items():
            answers = answer_batch(
                self._pools[gen], [tickets[i].query for i in idxs],
                solver=self.solver, delta=self.delta, alpha=self.alpha)
            for i, a in zip(idxs, answers):
                out[i] = a
            self._inflight[gen] -= len(idxs)
        self._retire_drained()
        return out  # type: ignore[return-value]

    def serve(self, queries: Sequence[Query], *,
              deadline_s: Optional[float] = None,
              clock: Callable[[], float] = time.monotonic
              ) -> list[Answer]:
        """Admission loop: answer the batch, then re-admit any
        uncertified query against refreshed (theta-doubled)
        generations until its certificate clears or ``max_theta`` is
        reached (the amortized OPIM-C doubling loop).

        ``deadline_s`` bounds the wall-clock spent doubling: when the
        deadline (or ``max_theta``) cuts the loop short, the
        still-uncertified answers are returned marked
        ``degraded=True`` — each carries its honest ``opim.certify``
        lower bound (``sigma_lower`` / ``guarantee``) at the theta it
        reached, instead of the loop spinning or raising."""
        start = clock()
        tickets = [self.admit(q) for q in queries]
        answers = self.answer(tickets)
        while True:
            retry = [i for i, a in enumerate(answers)
                     if not a.certified]
            if not retry:
                return answers
            out_of_time = (deadline_s is not None
                           and clock() - start >= deadline_s)
            if self.pool.theta >= self.max_theta or out_of_time:
                for i in retry:
                    answers[i] = answers[i]._replace(degraded=True)
                return answers
            self.refresh()
            redo = self.answer([self.admit(queries[i]) for i in retry])
            for i, a in zip(retry, redo):
                answers[i] = a


def answer_with_retry(service: InfluenceService,
                      tickets: Sequence[Ticket], *, retries: int = 3,
                      backoff_s: float = 0.0,
                      sleep_fn: Callable[[float], None] = time.sleep
                      ) -> list[Answer]:
    """``service.answer`` with bounded retry:

    * :class:`StaleGenerationError` (a concurrent refresh retired a
      ticket's generation between admit and answer) — release the
      surviving tickets and re-admit every query on the CURRENT
      generation, then retry;
    * :class:`InjectedFault` (a transient injected failure at the
      ``service.answer`` site) — plain retry: the plan's occurrence
      counter has advanced, and ``answer`` raises before consuming any
      in-flight count, so the retry is exact.

    Exponential backoff ``backoff_s * 2**(attempt-1)`` through the
    injectable ``sleep_fn`` (tests pass a recorder, never a real
    sleep).  Re-raises the last error when the budget is exhausted.
    """
    tickets = list(tickets)
    last: Optional[Exception] = None
    for attempt in range(retries + 1):
        if attempt and backoff_s:
            sleep_fn(backoff_s * (2 ** (attempt - 1)))
        try:
            return service.answer(tickets)
        except StaleGenerationError as e:
            last = e
            service.release([t for t in tickets
                             if t.generation in service._pools])
            tickets = [service.admit(t.query) for t in tickets]
        except InjectedFault as e:
            last = e
    raise last  # type: ignore[misc]
