"""The plain reference against JAX's stream and the program, on the
CPU at a tiny size: exact agreement where the program is sound."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny  # noqa: F401
from bench import gen
from bench.reference import cover, graph as rg, prng, rrr
from bench.reference import round as rround
from bench.reference import service as rservice

SEED = 2 ** 31 + 99
N = 512


def jkey(kd):
    return jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32))


def kdata(k):
    return tuple(int(x) for x in jax.random.key_data(k))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3])
def test_keys_follow_jax(seed):
    kd = prng.key_from_seed(seed)
    k = jkey(kd)
    assert kdata(jax.random.fold_in(k, 12345)) == prng.fold_in(kd, 12345)
    assert [kdata(x) for x in jax.random.split(k)] == prng.split(kd)


@pytest.mark.parametrize("shape", [(7,), (3, 50, 7), (2, 1000, 22)])
def test_uniform_follows_jax(shape):
    kd = prng.key_from_seed(2 ** 31 + 1)
    got = np.asarray(jax.random.uniform(jkey(kd), shape))
    idx = np.arange(int(np.prod(shape)))
    assert np.array_equal(got.ravel(), prng.uniform_f32(prng.bits32(kd, idx)))


def test_high_counter_word_follows_jax():
    """Indices past 2**32 put their high word in the first counter."""
    from jax._src import prng as jprng
    kd = prng.key_from_seed(5)
    hi = np.array([1, 3, 7], np.uint32)
    lo = np.array([0, 9, 2 ** 32 - 1], np.uint32)
    y1, y2 = jprng.threefry2x32_p.bind(
        jnp.uint32(kd[0]), jnp.uint32(kd[1]), jnp.asarray(hi),
        jnp.asarray(lo))
    idx = hi.astype(np.uint64) << np.uint64(32) | lo.astype(np.uint64)
    assert np.array_equal(np.asarray(y1) ^ np.asarray(y2),
                          prng.bits32(kd, idx))


@pytest.mark.parametrize("n,span", [(1000, 262144), (1000, 200),
                                    (64, 3), (1000, 317080),
                                    (1000, 65537)])
def test_randint_follows_jax(n, span):
    kd = prng.key_from_seed(11)
    got = np.asarray(jax.random.randint(jkey(kd), (n,), 0, span))
    assert np.array_equal(got, prng.randint(kd, n, 0, span))


@pytest.mark.parametrize("n", [300, 262144, 317080])
def test_permutation_follows_jax(n):
    kd = prng.key_from_seed(13)
    assert np.array_equal(np.asarray(jax.random.permutation(jkey(kd), n)),
                          prng.permutation(kd, n))


@pytest.fixture(scope="module")
def tiny():
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    spec = {"generator": "gnm_undirected", "n": N, "edges": 4 * N,
            "structure_seed": 0}
    src, dst = gen.edge_list(spec)
    g = from_edge_list(src, dst, N, seed=SEED)
    return dict(src=src, dst=dst, g=g, adj=padded_adjacency(g),
                fwd=padded_forward_adjacency(g),
                tab=rg.tables(src, dst, N, SEED))


def test_tables_match_program(tiny):
    nbr, prob, wt = tiny["adj"]
    tab = tiny["tab"]
    assert np.array_equal(tab.nbr, np.asarray(nbr))
    assert np.array_equal(tab.prob, np.asarray(prob))
    assert np.array_equal(tab.wt, np.asarray(wt))


def _program_sets(tiny, model, kb, roots):
    from repro.core import bitset
    from repro.core.rrr import rrr_batch_packed
    nbr, prob, wt = tiny["adj"]
    x = rrr_batch_packed(nbr, prob, wt, *tiny["fwd"], jnp.asarray(roots),
                         jkey(kb), model=model, max_steps=32, coin_chunk=32)
    return np.asarray(bitset.unpack_words(x, len(roots))).T


@pytest.mark.parametrize("model", ["IC", "LT"])
@pytest.mark.parametrize("i", [0, 1])
def test_sampler_matches_program(tiny, model, i):
    tab = tiny["tab"]
    kr, kb = prng.split(prng.fold_in(prng.key_from_seed(SEED), i))
    roots = prng.randint(kr, 128, 0, N)
    b, v, _ = rrr.sample_batch(tab, roots, kb, model=model, max_steps=32,
                               cumw=rg.lt_thresholds(tab.wt))
    mine = np.zeros((128, N), bool)
    mine[b, v] = True
    assert np.array_equal(mine, _program_sets(tiny, model, kb, roots))


def test_greedy_lowest_index_on_ties():
    inc = cover.Incidence(np.array([3, 3, 1, 1, 0]),
                          np.array([0, 1, 2, 3, 0]), 4, 4)
    rows, gains = cover.greedy(inc, 3)
    assert list(rows) == [1, 3, -1] and list(gains) == [2, 2, 0]
    rows, _ = cover.greedy(inc, 2, excluded=(1,))
    assert list(rows) == [3, -1]


@pytest.mark.parametrize("model", ["IC", "LT"])
def test_round_matches_program(tiny, model):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core import greediris
    from repro.launch.mesh import make_im_mesh
    mesh = make_im_mesh(1)
    fn, _, theta = greediris.build_round(
        mesh, ("machines",), n=N, theta=256, k=8,
        max_degree=tiny["g"].max_in_degree(), model=model,
        aggregate="gather", sampler="packed", fwd=tiny["fwd"],
        sample_chunks=2, solver="scan", max_steps=32)
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(a, rep) for a in tiny["adj"]]
    run = jax.jit(fn)
    tab = tiny["tab"]
    for s in range(2):
        kd = prng.key_from_seed(1000 + s)
        out = run(*args, jkey(kd))
        ref = rround.run_round(tab, kd, theta=theta, chunks=2, k=8,
                               model=model, max_steps=32, delta=0.077,
                               cumw=rg.lt_thresholds(tab.wt))
        assert np.array_equal(np.asarray(out.seeds), ref.seeds)
        assert int(out.coverage) == ref.coverage
        assert int(out.global_coverage) == ref.global_coverage
        assert int(out.best_local_coverage) == ref.best_local_coverage


def test_service_matches_program(tiny):
    from repro.core.service import InfluenceService, Query
    kd = prng.key_from_seed(SEED)
    svc = InfluenceService(tiny["g"], jkey(kd), theta0=256, max_theta=256,
                           slab=128, solver="resident", sampler="packed")
    svc.refresh()
    r1, r2, _ = rservice.sample_pool(tiny["tab"], kd, theta=256, slab=128,
                                  model="IC", max_steps=32)
    ref = rservice.Answerer(r1, r2, k_max=8)
    queries = [Query(k=8), Query(k=3, budget=40.0),
               Query(k=5, excluded=(int(ref.rows[0]), int(ref.rows[2]))),
               Query(k=8, excluded=(1, 2, 3))]
    for q, a in zip(queries, svc.answer([svc.admit(q) for q in queries])):
        r = ref.answer(q.k, q.excluded, q.budget, q.eps)
        assert np.array_equal(a.seeds, r.seeds)
        assert (a.k_used, a.coverage, a.certified) == (r.k_used, r.coverage,
                                                       r.certified)
        assert (a.sigma_lower, a.sigma_upper, a.guarantee) == (
            r.sigma_lower, r.sigma_upper, r.guarantee)


def test_bf16_uniform_follows_jax():
    kd = prng.key_from_seed(2 ** 31 + 9)
    got = np.asarray(jax.random.uniform(jkey(kd), (4, 300),
                                        dtype=jnp.bfloat16), np.float32)
    mine = prng.uniform_bf16(prng.bits32(kd, np.arange(1200)))
    assert np.array_equal(got.ravel(), mine)
