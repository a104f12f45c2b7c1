"""Online serving (repro.core.service): batched-vs-sequential
bit-identity, seed-constraints, pool lifecycle, budget truncation."""
import jax
import numpy as np
import pytest

from repro.core import maxcover
from repro.core import service as svc
from repro.core.service import (EmptyPoolError, InfluenceService, Query,
                                StaleGenerationError)
from repro.graphs.csr import from_edge_list


def make_test_graph(n=37, m=150, seed=0, p=0.3):
    """Small dense-ish digraph with a deliberately non-word-aligned
    vertex count (default n=37) and explicit edge probabilities (so
    mutation tests can extend the edge list without perturbing the
    probability stream of untouched edges)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    probs = np.full(int(keep.sum()), p)
    return from_edge_list(src[keep], dst[keep], n, probs=probs), \
        src[keep], dst[keep], probs


@pytest.fixture(scope="module")
def graph():
    return make_test_graph()[0]


@pytest.fixture(scope="module")
def pool(graph):
    return svc.make_pool(graph, jax.random.PRNGKey(42), theta=256,
                         slab=128)


# A B=8 trace with mixed per-query k, mixed-length exclusion sets and
# a couple of spread budgets — the acceptance-criterion batch.
TRACE = [
    Query(k=3),
    Query(k=5, excluded=(0, 4, 9)),
    Query(k=2, excluded=(1,)),
    Query(k=4, budget=6.0),
    Query(k=1),
    Query(k=5, excluded=(2, 3, 5, 7, 11)),
    Query(k=3, budget=3.5, excluded=(6,)),
    Query(k=4),
]


@pytest.mark.parametrize("solver", maxcover.SOLVERS)
def test_batch_bit_identical_to_sequential(pool, solver):
    """B=8 concurrent queries in ONE batched solve == the sequential
    answer_one reference, bit-for-bit, on every solver — with mixed
    per-query k and a non-word-aligned n=37."""
    batch = svc.answer_batch(pool, TRACE, solver=solver)
    for q, a in zip(TRACE, batch):
        one = svc.answer_one(pool, q, solver=solver)
        np.testing.assert_array_equal(a.seeds, one.seeds)
        assert a.k_used == one.k_used
        assert a.coverage == one.coverage
        assert a.spread == one.spread
        assert a.sigma_lower == one.sigma_lower
        assert a.sigma_upper == one.sigma_upper
        assert a.guarantee == one.guarantee
        assert a.certified == one.certified


def test_exclusion_widths_share_one_compiled_solve(pool):
    """Exclusion slots come in groups of EXCL_SLOTS: batches whose
    longest lists differ within a group solve at one width and the
    second compiles no new solve; a longer list takes the next group."""
    slots = svc.EXCL_SLOTS
    one = [Query(k=3, excluded=(1,)), Query(k=3)]
    five = [Query(k=3, excluded=(0, 4, 9, 2, 5)), Query(k=3)]
    many = [Query(k=3, excluded=tuple(range(2, 3 + slots)))]
    assert [svc._query_arrays(b, pool.n, pool.theta)[1].shape[1]
            for b in (one, five, many)] == [slots, slots, 2 * slots]
    svc.answer_batch(pool, one)
    compiled = maxcover._greedy_maxcover_batch._cache_size()
    svc.answer_batch(pool, five)
    assert maxcover._greedy_maxcover_batch._cache_size() == compiled


def test_solver_quad_agrees_on_batch(pool):
    """All four solvers produce the same batched answers."""
    per_solver = [svc.answer_batch(pool, TRACE, solver=s)
                  for s in maxcover.SOLVERS]
    for other in per_solver[1:]:
        for a, b in zip(per_solver[0], other):
            np.testing.assert_array_equal(a.seeds, b.seeds)
            assert a.coverage == b.coverage


def test_seed_constraint_excludes_already_seeded(pool):
    """Excluding the unconstrained winners (an earlier campaign's
    seeds) forces a disjoint seed set; unconstrained queries in the
    same batch are unaffected."""
    free = svc.answer_one(pool, Query(k=3))
    prior = tuple(int(s) for s in free.seeds if s >= 0)
    assert prior
    batch = svc.answer_batch(pool, [Query(k=3),
                                    Query(k=3, excluded=prior)])
    np.testing.assert_array_equal(batch[0].seeds, free.seeds)
    constrained = [int(s) for s in batch[1].seeds if s >= 0]
    assert not set(constrained) & set(prior)
    # (no coverage ordering asserted: greedy is not optimal, so the
    # constrained solve can legitimately cover MORE than the free one)


def test_mixed_k_is_prefix_consistent(pool):
    """A k=2 answer is exactly the first 2 picks of the k=5 answer
    (greedy prefix-consistency — what makes mixed-k batching exact)."""
    a5 = svc.answer_one(pool, Query(k=5))
    a2 = svc.answer_one(pool, Query(k=2))
    np.testing.assert_array_equal(a2.seeds, a5.seeds[:2])


def test_budget_truncation(pool):
    """A spread budget stops selection at the first seed whose running
    sketch estimate reaches it; a huge budget changes nothing."""
    full = svc.answer_one(pool, Query(k=5))
    assert full.k_used == 5
    # budget just under the 2-seed running estimate -> exactly 2 seeds
    sol = maxcover.greedy_maxcover(pool.r1, 5)
    csum = np.cumsum(np.asarray(sol.gains))
    two_spread = csum[1] * pool.n / pool.theta
    capped = svc.answer_one(pool, Query(k=5, budget=two_spread - 1e-6))
    assert capped.k_used == 2
    np.testing.assert_array_equal(capped.seeds[:2], full.seeds[:2])
    assert np.all(capped.seeds[2:] == -1)
    assert capped.coverage == int(csum[1])
    uncapped = svc.answer_one(pool, Query(k=5, budget=float(pool.n)))
    np.testing.assert_array_equal(uncapped.seeds, full.seeds)


def test_budget_truncation_batched_matches(pool):
    sol = maxcover.greedy_maxcover(pool.r1, 4)
    csum = np.cumsum(np.asarray(sol.gains))
    queries = [Query(k=4, budget=float(c * pool.n / pool.theta))
               for c in csum]
    batch = svc.answer_batch(pool, queries)
    for j, a in enumerate(batch):
        assert a.k_used == j + 1
        one = svc.answer_one(pool, queries[j])
        np.testing.assert_array_equal(a.seeds, one.seeds)


def test_refresh_preserves_existing_columns(pool):
    """Growth appends generation-salted slabs; every existing column
    is carried over bit-identically (slab-keyed sampling)."""
    p2 = svc.refresh(pool)
    assert p2.theta == 2 * pool.theta
    assert p2.generation == pool.generation + 1
    np.testing.assert_array_equal(
        np.asarray(p2.r1)[:, :pool.words], np.asarray(pool.r1))
    np.testing.assert_array_equal(
        np.asarray(p2.r2)[:, :pool.words], np.asarray(pool.r2))
    assert list(p2.salt) == [0, 0, 1, 1]
    # and the appended slabs match a from-scratch pool of the same
    # seed exactly where the slab salts agree (pure key-derived)
    p3 = svc.refresh(pool)
    np.testing.assert_array_equal(np.asarray(p2.r1), np.asarray(p3.r1))


def test_refresh_must_grow(pool):
    with pytest.raises(ValueError):
        svc.refresh(pool, pool.theta)


def test_mutation_resamples_only_affected_slabs(graph, pool):
    """Edge insertion: slabs whose samples contain the new edge's head
    are resampled on the new graph; all other columns carry over."""
    _, src, dst, probs = make_test_graph()
    u, v = 0, 20
    g2 = from_edge_list(np.append(src, u), np.append(dst, v),
                        graph.num_vertices,
                        probs=np.append(probs, 0.9))
    stale = set(int(s) for s in svc.affected_slabs(pool, [v]))
    p2 = svc.refresh_mutated(pool, g2, [v])
    assert p2.generation == pool.generation + 1
    wps = pool.slab // 32
    r1o, r1n = np.asarray(pool.r1), np.asarray(p2.r1)
    for s in range(pool.theta // pool.slab):
        if s in stale:
            assert p2.salt[s] == p2.generation
        else:
            assert p2.salt[s] == pool.salt[s]
            np.testing.assert_array_equal(r1o[:, s*wps:(s+1)*wps],
                                          r1n[:, s*wps:(s+1)*wps])


def test_mutation_untouched_vertices_keep_pool(graph, pool):
    """A mutation whose head no sample contains changes nothing but
    the generation tag."""
    p2 = svc.refresh_mutated(pool, graph, [])
    assert p2.generation == pool.generation + 1
    np.testing.assert_array_equal(np.asarray(p2.r1),
                                  np.asarray(pool.r1))


def test_empty_pool_raises_and_admit_fills(graph):
    service = InfluenceService(graph, jax.random.PRNGKey(7), theta0=128,
                               max_theta=512, slab=128)
    assert service.pool.theta == 0
    with pytest.raises(EmptyPoolError):
        svc.answer_batch(service.pool, [Query(k=2)])
    ticket = service.admit(Query(k=2))   # empty-pool admission -> fill
    assert service.pool.theta == 128
    assert ticket.generation == service.generation == 1
    (ans,) = service.answer([ticket])
    assert ans.generation == 1 and ans.k_used == 2


def test_generation_drain_and_eviction(graph):
    """Tickets admitted before a refresh complete on their OLD
    generation's pool (drain); once drained the generation retires and
    answering against it raises StaleGenerationError."""
    service = InfluenceService(graph, jax.random.PRNGKey(7), theta0=128,
                               max_theta=1024, slab=128)
    t_old = service.admit(Query(k=3))
    old_gen = t_old.generation
    old_pool = service.pool
    service.refresh()
    assert service.generation == old_gen + 1
    assert old_gen in service._pools          # draining, not evicted
    t_new = service.admit(Query(k=3))
    a_old, a_new = service.answer([t_old, t_new])
    assert a_old.generation == old_gen
    assert a_new.generation == service.generation
    # the drained answer used the old pool's samples, bit-for-bit
    ref = svc.answer_one(old_pool, Query(k=3), solver=service.solver)
    np.testing.assert_array_equal(a_old.seeds, ref.seeds)
    # drained -> retired -> stale
    assert old_gen not in service._pools
    stale = service.admit(Query(k=3))._replace(generation=old_gen)
    with pytest.raises(StaleGenerationError):
        service.answer([stale])


def test_serve_refreshes_until_certified(graph):
    """serve() doubles theta for uncertified answers; a generous eps
    certifies within the cap and later generations answer it."""
    service = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                               max_theta=2048, slab=128)
    answers = service.serve([Query(k=3, eps=0.45),
                             Query(k=2, eps=0.45, excluded=(1, 2))])
    assert all(a.certified for a in answers)
    assert service.pool.theta <= 2048
    assert all(a.generation == service.generation for a in answers)


def test_admit_validates(graph):
    service = InfluenceService(graph, jax.random.PRNGKey(7), theta0=128,
                               max_theta=512, slab=128)
    with pytest.raises(ValueError):
        service.admit(Query(k=0))
    with pytest.raises(ValueError):
        service.admit(Query(k=graph.num_vertices + 1))
    with pytest.raises(ValueError):
        service.admit(Query(k=2, budget=float(graph.num_vertices + 1)))
    with pytest.raises(ValueError):
        svc.answer_batch(svc.make_pool(graph, jax.random.PRNGKey(1),
                                       theta=128, slab=128),
                         [Query(k=2, excluded=(graph.num_vertices,))])


def test_per_query_state_bytes_model():
    # covered words + selected rows + seed slots + gain slots +
    # exclusion slots, 4B each
    assert svc.per_query_state_bytes(8, 3, 1) == 4 * (8 + 3 * 8 + 3 + 3 + 1)


# ---------------------------------------------------------------------
# Recovery: snapshot/restore, from_pool, retry, degraded serve
# ---------------------------------------------------------------------

def test_pool_snapshot_restore_bit_identical(graph, tmp_path):
    """pool_state -> CheckpointStore -> pool_from_state reconstructs
    the pool bit-for-bit, INCLUDING the PRNG stream: a post-restore
    refresh appends the same salted slabs as the original would."""
    from repro.checkpoint.store import CheckpointStore
    pool = svc.make_pool(graph, jax.random.PRNGKey(7), theta=256,
                         slab=128)
    pool = svc.refresh(pool, 512)          # generation 1, mixed salts
    store = CheckpointStore(str(tmp_path))
    step = svc.snapshot_pool(store, pool)
    assert step == pool.generation
    p2, got = svc.restore_pool(store, graph)
    assert got == step
    np.testing.assert_array_equal(np.asarray(pool.r1), np.asarray(p2.r1))
    np.testing.assert_array_equal(np.asarray(pool.r2), np.asarray(p2.r2))
    np.testing.assert_array_equal(pool.salt, p2.salt)
    assert (p2.theta, p2.generation, p2.slab, p2.model, p2.sampler) == \
        (pool.theta, pool.generation, pool.slab, pool.model, pool.sampler)
    a, b = svc.refresh(pool, 1024), svc.refresh(p2, 1024)
    np.testing.assert_array_equal(np.asarray(a.r1), np.asarray(b.r1))
    np.testing.assert_array_equal(a.salt, b.salt)


def test_pool_snapshot_restore_typed_key(graph, tmp_path):
    from repro.checkpoint.store import CheckpointStore
    pool = svc.make_pool(graph, jax.random.key(11), theta=128, slab=128)
    store = CheckpointStore(str(tmp_path))
    svc.snapshot_pool(store, pool)
    p2, _ = svc.restore_pool(store, graph)
    assert jax.numpy.issubdtype(p2.key.dtype, jax.dtypes.prng_key)
    np.testing.assert_array_equal(np.asarray(svc.refresh(pool, 256).r1),
                                  np.asarray(svc.refresh(p2, 256).r1))


def test_restore_pool_empty_store(graph, tmp_path):
    from repro.checkpoint.store import CheckpointStore
    pool, step = svc.restore_pool(CheckpointStore(str(tmp_path)), graph)
    assert pool is None and step == -1


def test_from_pool_service_resumes_bit_identical(graph):
    """A service rebuilt around a restored pool answers exactly like
    the one that never died, and future refreshes continue the same
    generation/salt stream."""
    s1 = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                          max_theta=2048, slab=128)
    (a1,) = s1.answer([s1.admit(Query(k=3))])
    s2 = InfluenceService.from_pool(s1.pool, theta0=128, max_theta=2048)
    assert s2.generation == s1.generation
    (a2,) = s2.answer([s2.admit(Query(k=3))])
    np.testing.assert_array_equal(a1.seeds, a2.seeds)
    assert a1[1:] == a2[1:]
    s1.refresh(), s2.refresh()
    np.testing.assert_array_equal(np.asarray(s1.pool.r1),
                                  np.asarray(s2.pool.r1))


def test_answer_with_retry_injected_fault(graph):
    from repro.runtime.faults import FaultPlan, FaultSpec, InjectedFault
    plan = FaultPlan([FaultSpec("service.answer", "raise", at=1)])
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=2048, slab=128, fault_plan=plan)
    (ref,) = s.answer([s.admit(Query(k=3))])      # occurrence 0: clean
    sleeps = []
    (got,) = svc.answer_with_retry(s, [s.admit(Query(k=3))],
                                   backoff_s=0.5, sleep_fn=sleeps.append)
    np.testing.assert_array_equal(ref.seeds, got.seeds)
    assert sleeps == [0.5]                 # backoff recorded, not slept
    assert [e["site"] for e in plan.events] == ["service.answer"]
    # a persistent fault re-raises once the budget is exhausted
    plan2 = FaultPlan([FaultSpec("service.answer", "raise", at=i)
                       for i in range(4)])
    s2 = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                          max_theta=2048, slab=128, fault_plan=plan2)
    t = s2.admit(Query(k=2))
    with pytest.raises(InjectedFault):
        svc.answer_with_retry(s2, [t], retries=1, sleep_fn=lambda s: None)


def test_answer_with_retry_stale_generation(graph):
    """Tickets whose generation was retired are re-admitted on the
    current generation and answered there."""
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=2048, slab=128)
    t = s.admit(Query(k=3))
    s.release([t])          # drained -> next refresh retires gen
    s.refresh()
    with pytest.raises(StaleGenerationError):
        s.answer([t])
    (a,) = svc.answer_with_retry(s, [t])
    assert a.generation == s.generation
    (ref,) = s.answer([s.admit(Query(k=3))])
    np.testing.assert_array_equal(a.seeds, ref.seeds)


def test_release_drains_generation(graph):
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=2048, slab=128)
    t = s.admit(Query(k=3))
    gen = t.generation
    assert s.inflight(gen) == 1
    s.refresh()
    assert gen in s._pools                 # draining
    s.release([t])
    assert gen not in s._pools             # retired on release
    assert s.inflight(gen) == 0


def test_serve_deadline_returns_degraded_with_bound(graph):
    """A deadline cuts the theta-doubling loop short: uncertified
    answers come back degraded=True, carrying their opim.certify
    lower bound instead of looping or raising."""
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=1 << 14, slab=128)
    ticks = iter([0.0, 10.0, 20.0, 30.0, 40.0, 50.0])
    answers = s.serve([Query(k=3, eps=0.0)], deadline_s=5.0,
                      clock=lambda: next(ticks))
    (a,) = answers
    assert a.degraded and not a.certified
    assert a.sigma_lower > 0 and 0 < a.guarantee < 1
    assert s.pool.theta < s.max_theta      # stopped by time, not theta


def test_serve_max_theta_marks_degraded(graph):
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=256, slab=128)
    answers = s.serve([Query(k=3, eps=0.0), Query(k=2, eps=0.45)])
    for a in answers:
        assert a.degraded == (not a.certified)
    assert any(a.degraded for a in answers)


def test_certified_serve_answers_not_degraded(graph):
    s = InfluenceService(graph, jax.random.PRNGKey(3), theta0=128,
                         max_theta=2048, slab=128)
    answers = s.serve([Query(k=3, eps=0.45)])
    assert all(a.certified and not a.degraded for a in answers)


def test_sampler_slab_fill_site_fires_per_slab(graph):
    from repro.runtime.faults import FaultPlan, InjectedFault
    plan = FaultPlan([])
    svc.make_pool(graph, jax.random.PRNGKey(1), theta=256, slab=128,
                  plan=plan)
    # 2 slabs x 2 OPIM halves = 4 probes
    assert plan.occurrences("sampler.slab_fill") == 4
