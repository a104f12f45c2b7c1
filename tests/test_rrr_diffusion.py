import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset
from repro.core.diffusion import influence
from repro.core.rrr import rrr_batch, sample_incidence_host
from repro.graphs import generators
from repro.graphs.csr import from_edge_list, padded_adjacency


def test_star_graph_hub_dominates():
    """Hub->leaf edges with p=1: every RRR set contains the hub."""
    g = generators.star(50)
    X, theta = sample_incidence_host(g, 256, jax.random.key(0), model="IC")
    freq = np.asarray(bitset.coverage_size(X))
    assert freq[0] == theta                      # hub in every sample
    assert freq[1:].max() <= theta // 4          # leaves only their own


def test_rrr_contains_root():
    g = generators.erdos_renyi(100, 4.0, seed=0)
    nbr, prob, wt = padded_adjacency(g)
    roots = jnp.arange(32)
    vis = rrr_batch(nbr, prob, wt, roots, jax.random.key(1), model="IC")
    assert bool(jnp.all(vis[jnp.arange(32), roots]))


def test_rrr_reachability_closure():
    """RRR sets only contain vertices with a directed path to the root."""
    # chain 0 -> 1 -> 2 (p=1); reverse-reachable(2) = {0,1,2};
    # reverse-reachable(0) = {0}
    g = from_edge_list(np.array([0, 1]), np.array([1, 2]), 3,
                       probs=np.ones(2, dtype=np.float32))
    nbr, prob, wt = padded_adjacency(g)
    vis = rrr_batch(nbr, prob, wt, jnp.asarray([2, 0]), jax.random.key(0),
                    model="IC")
    np.testing.assert_array_equal(np.asarray(vis[0]), [True, True, True])
    np.testing.assert_array_equal(np.asarray(vis[1]), [True, False, False])


def test_lt_sets_no_larger_than_one_inneighbor_chain():
    """LT live-edge picks <= 1 in-edge per vertex: RRR set size <= path
    length bound (no branching)."""
    g = generators.erdos_renyi(100, 6.0, seed=2)
    nbr, prob, wt = padded_adjacency(g)
    vis_lt = rrr_batch(nbr, prob, wt, jnp.arange(64), jax.random.key(3),
                       model="LT", max_steps=16)
    sizes = np.asarray(vis_lt).sum(axis=1)
    assert sizes.max() <= 17   # root + one per step (chain, no tree)


def test_rrr_frequency_tracks_influence():
    """RIS theory: P(v in RRR) = sigma({v}) / n.  The top-frequency
    vertices should have at least the MC influence of the bottom ones
    (tolerance for MC noise on small spreads)."""
    g = generators.preferential_attachment(120, 3, seed=4)
    X, theta = sample_incidence_host(g, 2048, jax.random.key(4),
                                     model="IC")
    freq = np.asarray(bitset.coverage_size(X))
    order = np.argsort(freq)
    key = jax.random.key(5)
    inf_top = float(influence(g, order[-5:].copy(), key, num_sims=96))
    inf_low = float(influence(g, order[:5].copy(), key, num_sims=96))
    assert inf_top >= 0.9 * inf_low


def test_influence_bounds():
    g = generators.erdos_renyi(80, 5.0, seed=6)
    s = float(influence(g, np.array([0, 1, 2]), jax.random.key(0),
                        num_sims=16))
    assert 3.0 <= s <= 80.0


def test_lt_influence_runs():
    g = generators.erdos_renyi(60, 5.0, seed=7)
    s = float(influence(g, np.array([0]), jax.random.key(1), model="LT",
                        num_sims=16))
    assert 1.0 <= s <= 60.0


# ---------------------------------------------------------------------
# Packed / kernel sampler parity (tentpole acceptance criteria)
# ---------------------------------------------------------------------
import pytest

from repro.core.rrr import rrr_batch_packed, sample_incidence
from repro.graphs.csr import padded_forward_adjacency


def _parity_graphs():
    # non-word-aligned n, skewed degrees (star: hub in-degree 0,
    # leaves in-degree 1... plus a preferential-attachment heavy tail)
    return [generators.erdos_renyi(37, 4.0, seed=0),
            generators.star(33),
            generators.preferential_attachment(50, 3, seed=4)]


@pytest.mark.parametrize("model", ("IC", "LT"))
@pytest.mark.parametrize("batch,max_steps", ((64, 32), (40, 2)))
def test_packed_sampler_bit_identical_to_dense(model, batch, max_steps):
    """pack(dense_visited.T) == packed_visited bit-for-bit, across
    non-word-aligned batch (pad bits stay zero), skewed degrees, and
    max_steps cutoffs — same key => identical packed incidence."""
    for g in _parity_graphs():
        n = g.num_vertices
        nbr, prob, wt = padded_adjacency(g)
        fwd = padded_forward_adjacency(g)
        roots = jax.random.randint(jax.random.key(7), (batch,), 0, n)
        key = jax.random.key(5)
        dense = rrr_batch(nbr, prob, wt, roots, key, model=model,
                          max_steps=max_steps)
        packed = rrr_batch_packed(nbr, prob, wt, *fwd, roots, key,
                                  model=model, max_steps=max_steps)
        np.testing.assert_array_equal(
            np.asarray(bitset.pack_bool_matrix(dense.T)),
            np.asarray(packed))


@pytest.mark.parametrize("model", ("IC", "LT"))
def test_kernel_sampler_bit_identical_to_packed(model):
    """The fused Pallas expansion (expand="kernel") reproduces the
    packed JAX path bit-for-bit (and hence the dense path)."""
    g = generators.erdos_renyi(45, 5.0, seed=2)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    roots = jax.random.randint(jax.random.key(1), (64,), 0, 45)
    key = jax.random.key(9)
    jax_path = rrr_batch_packed(nbr, prob, wt, *fwd, roots, key,
                                model=model, max_steps=8)
    kern = rrr_batch_packed(nbr, prob, wt, *fwd, roots, key,
                            model=model, max_steps=8, expand="kernel")
    np.testing.assert_array_equal(np.asarray(jax_path), np.asarray(kern))


def test_sample_incidence_sampler_triad_identical():
    g = generators.erdos_renyi(60, 4.0, seed=3)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    key = jax.random.key(4)
    want = sample_incidence(nbr, prob, wt, key, theta=96, n=60,
                            model="IC", max_steps=8)
    for sampler in ("packed", "kernel"):
        got = sample_incidence(nbr, prob, wt, key, theta=96, n=60,
                               model="IC", max_steps=8, sampler=sampler,
                               fwd=fwd)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_rrr_batch_sampler_shim_returns_dense_bool():
    """rrr_batch(sampler="packed") unpacks to the dense bool layout."""
    g = generators.erdos_renyi(30, 3.0, seed=5)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    roots = jnp.arange(32)
    key = jax.random.key(2)
    dense = rrr_batch(nbr, prob, wt, roots, key, model="IC", max_steps=4)
    via = rrr_batch(nbr, prob, wt, roots, key, model="IC", max_steps=4,
                    sampler="packed", fwd=fwd)
    assert via.dtype == jnp.bool_ and via.shape == dense.shape
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(via))


def test_coin_chunk_threads_and_keeps_parity():
    """coin_chunk is part of the IC PRNG stream (acts like a seed):
    dense/packed stay bit-identical at any fixed value, and changing
    it changes the sampled sets."""
    g = generators.preferential_attachment(40, 4, seed=6)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    roots = jax.random.randint(jax.random.key(3), (32,), 0, 40)
    key = jax.random.key(8)
    outs = {}
    for cc in (2, 32):
        dense = rrr_batch(nbr, prob, wt, roots, key, model="IC",
                          max_steps=6, coin_chunk=cc)
        packed = rrr_batch_packed(nbr, prob, wt, *fwd, roots, key,
                                  model="IC", max_steps=6, coin_chunk=cc)
        np.testing.assert_array_equal(
            np.asarray(bitset.pack_bool_matrix(dense.T)),
            np.asarray(packed))
        outs[cc] = np.asarray(packed)
    assert not np.array_equal(outs[2], outs[32])


def test_sample_incidence_host_trims_to_reported_theta():
    """Satellite regression: a non-multiple-of-256 theta (tail batch
    rounded up to whole words) must come back trimmed to the rounded
    theta the function reports — 32 * X.shape[1] == theta, always."""
    g = generators.erdos_renyi(40, 4.0, seed=7)
    key = jax.random.key(0)
    for batch in (96, 100):       # word-aligned and unaligned batches
        x, theta = sample_incidence_host(g, 300, key, batch=batch)
        assert theta == 320                      # ceil32(300)
        assert x.shape == (40, theta // 32)
    x256, theta256 = sample_incidence_host(g, 300, key)   # batch=256
    assert theta256 == 320 and x256.shape[1] == 10


def test_sample_incidence_host_packed_matches_dense():
    g = generators.erdos_renyi(40, 4.0, seed=8)
    key = jax.random.key(1)
    want, theta_d = sample_incidence_host(g, 128, key, batch=64)
    got, theta_p = sample_incidence_host(g, 128, key, batch=64,
                                         sampler="packed")
    assert theta_d == theta_p
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------
# LT live-edge semantics (satellite)
# ---------------------------------------------------------------------

def _chain_graph(n):
    """0 -> 1 -> ... -> n-1; each vertex has exactly one in-edge whose
    LT weight normalizes to 1.0, so the live-edge chain is
    deterministic."""
    return from_edge_list(np.arange(n - 1), np.arange(1, n), n,
                          probs=np.ones(n - 1, dtype=np.float32))


def test_lt_chain_follows_exactly_one_in_edge():
    """Live-edge chain semantics: with a single weight-1 in-edge per
    vertex, RRR(root) under LT is exactly the ancestor chain
    {0..root} — every vertex follows precisely one in-edge."""
    n = 12
    g = _chain_graph(n)
    nbr, prob, wt = padded_adjacency(g)
    roots = jnp.asarray([0, 3, n - 1])
    vis = rrr_batch(nbr, prob, wt, roots, jax.random.key(0), model="LT",
                    max_steps=n)
    for i, r in enumerate([0, 3, n - 1]):
        want = np.zeros(n, dtype=bool)
        want[:r + 1] = True
        np.testing.assert_array_equal(np.asarray(vis[i]), want)


def test_lt_max_steps_truncation():
    """max_steps cuts the chain after exactly max_steps expansions:
    root + max_steps ancestors survive, dense and packed alike."""
    n = 12
    g = _chain_graph(n)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    roots = jnp.full((32,), n - 1, dtype=jnp.int32)
    for steps in (1, 3):
        vis = rrr_batch(nbr, prob, wt, roots, jax.random.key(1),
                        model="LT", max_steps=steps)
        sizes = np.asarray(vis).sum(axis=1)
        np.testing.assert_array_equal(sizes, steps + 1)
        assert bool(vis[0, n - 1]) and not bool(vis[0, n - 2 - steps])
        packed = rrr_batch_packed(nbr, prob, wt, *fwd, roots,
                                  jax.random.key(1), model="LT",
                                  max_steps=steps)
        np.testing.assert_array_equal(
            np.asarray(bitset.pack_bool_matrix(vis.T)),
            np.asarray(packed))


def test_edgeless_graph_rrr_is_root_only():
    """Review regression: d_max == 0 (no edges at all) must not crash
    the coin-chunk solve — every sampler returns RRR(root) = {root}."""
    g = from_edge_list(np.array([], dtype=np.int64),
                       np.array([], dtype=np.int64), 5)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    assert nbr.shape == (5, 0) and fwd[0].shape == (5, 0)
    roots = jnp.asarray([0, 3, 3, 4], dtype=jnp.int32)
    for model in ("IC", "LT"):
        dense = rrr_batch(nbr, prob, wt, roots, jax.random.key(0),
                          model=model)
        np.testing.assert_array_equal(
            np.asarray(dense),
            np.eye(5, dtype=bool)[np.asarray(roots)])
        for expand in ("jax", "kernel"):
            packed = rrr_batch_packed(nbr, prob, wt, *fwd, roots,
                                      jax.random.key(0), model=model,
                                      expand=expand)
            np.testing.assert_array_equal(
                np.asarray(bitset.pack_bool_matrix(dense.T)),
                np.asarray(packed))


# ---------------------------------------------------------------------
# Frontier coin draw: IC steps with a small frontier draw coins for its
# pairs only, bit-identical to the dense draw
# ---------------------------------------------------------------------
from repro.core import rrr as rrr_mod


def _p1_graph(src, dst, n):
    return from_edge_list(np.asarray(src), np.asarray(dst), n,
                          probs=np.ones(len(src), dtype=np.float32))


def _deep_graph():
    """Deep IC sets: G(n, m) with p = 0.2, so frontiers of several
    vertices per sample meet in-neighbors they share."""
    g = generators.erdos_renyi(60, 6.0, seed=9)
    dst = np.repeat(np.arange(60), np.diff(np.asarray(g.indptr)))
    return from_edge_list(np.asarray(g.indices), dst, 60,
                          probs=np.full(len(dst), 0.2, np.float32))


def _frontier_cases():
    n = 20                          # complete graph, p = 1: the second
    src, dst = np.nonzero(~np.eye(n, dtype=bool))    # step overflows
    return {
        "parity_graphs": [(g, 64, None) for g in _parity_graphs()],
        "dense_fallback": [(_p1_graph(src, dst, n), 64, None)],
        # 3 -> 1 -> 0 and 3 -> 2 -> 0: both frontier vertices of each
        # sample reach 3 in the same step
        "duplicate_hits": [(_p1_graph([3, 3, 1, 2], [1, 2, 0, 0], 4), 40,
                            np.zeros(40, np.int32))],
        "unaligned_batch": [(_deep_graph(), b, None) for b in (40, 72)],
        # 1024 roots at 0, each reaching 1 and 2, which reach 3 at p =
        # 0.5: the first step's 2048 hits and the second step's 2048
        # pairs take two update blocks each
        "update_blocks": [(from_edge_list(
            np.array([1, 2, 3, 3]), np.array([0, 0, 1, 2]), 4,
            probs=np.array([1, 1, 0.5, 0.5], np.float32)), 1024,
            np.zeros(1024, np.int32))],
    }


@pytest.mark.parametrize("kernel", (False, True))
@pytest.mark.parametrize("case", ("parity_graphs", "dense_fallback",
                                  "duplicate_hits", "unaligned_batch",
                                  "update_blocks"))
def test_frontier_steps_bit_identical_to_dense(case, kernel):
    """The packed engine's frontier steps (and, where the frontier
    outgrows their capacity, its dense fallback) give the dense
    sampler's incidence bit for bit; the counters say which ran."""
    for g, batch, roots in _frontier_cases()[case]:
        n = g.num_vertices
        nbr, prob, wt = padded_adjacency(g)
        fwd = padded_forward_adjacency(g)
        if roots is None:
            roots = jax.random.randint(jax.random.key(7), (batch,), 0, n)
        key = jax.random.key(5)
        dense, dcount = rrr_mod._rrr_batch_dense(
            nbr, prob, wt, roots, key, model="IC", max_steps=32,
            coin_chunk=32)
        packed, count = jax.jit(functools.partial(
            rrr_mod._rrr_batch_packed, model="IC", max_steps=32,
            coin_chunk=32, kernel=kernel))(nbr, prob, wt, *fwd, roots, key)
        np.testing.assert_array_equal(
            np.asarray(bitset.pack_bool_matrix(dense.T)),
            np.asarray(packed))
        steps, front = int(count.bfs_steps), int(count.frontier_steps)
        assert steps == int(dcount.bfs_steps) and int(
            dcount.frontier_steps) == 0
        d_pad = nbr.shape[1]
        plane = batch * n * d_pad
        if case == "dense_fallback":
            assert 0 < front < steps
            assert float(count.coins_drawn) > plane
        else:
            # every step fits: each sampled pair drew its d_pad coins
            # once, as the frontier that it joined was expanded
            assert front == steps
            assert float(count.coins_drawn) == d_pad * int(
                np.sum(np.asarray(bitset.popcount(packed))))
        if case == "duplicate_hits":
            assert steps == 3 and np.asarray(dense).all()
        if case == "update_blocks":
            assert steps == 3 and np.asarray(dense)[:, :3].all()
            assert 0 < np.asarray(dense)[:, 3].sum() < batch


def test_wide_index_matches_python_ints():
    """(hi, lo) of b * stride + off agree with Python's integers past
    2**32, at the round cell's sizes and at the extremes."""
    n, c = 317080, 21
    stride = n * c
    rng = np.random.default_rng(0)
    b = np.concatenate([rng.integers(0, 4096, 500), [0, 4095, 2**32 - 1]])
    off = np.concatenate([rng.integers(0, stride, 500),
                          [stride - 1, 0, 2**32 - 1]])
    for s in (stride, 2**32 - 1, 1):
        hi, lo = rrr_mod._wide_index(jnp.asarray(b, jnp.uint32), s,
                                     jnp.asarray(off, jnp.uint32))
        want = [int(x) * s + int(y) for x, y in zip(b, off)]
        got = [(int(h) << 32) | int(lv) for h, lv in
               zip(np.asarray(hi), np.asarray(lo))]
        assert got == want
        assert max(want) >= 2**32
    # the cell's largest index: 4096 x 317080 x 21 coins in one draw
    hi, lo = rrr_mod._wide_index(jnp.uint32(4095), stride,
                                 jnp.uint32(stride - 1))
    assert (int(hi) << 32) | int(lo) == 4096 * stride - 1 > 2**32


@pytest.mark.parametrize("typed", (True, False))
def test_uniform_at_matches_jax_uniform(typed):
    """The helper's uniforms equal ``jax.random.uniform`` element by
    element under ``fold_in(sub, c)`` for c > 0, for typed and raw
    Threefry keys."""
    k = jax.random.key(3) if typed else jax.random.PRNGKey(3)
    _, sub = jax.random.split(k)
    shape = (6, 7, 5)
    for c in (1, 4):
        kc = jax.random.fold_in(sub, c)
        want = np.asarray(jax.random.uniform(kc, shape))
        b, v, j = np.meshgrid(*map(np.arange, shape), indexing="ij")
        hi, lo = rrr_mod._wide_index(
            jnp.asarray(b, jnp.uint32), shape[1] * shape[2],
            jnp.asarray(v * shape[2] + j, jnp.uint32))
        got = np.asarray(rrr_mod._uniform_at(kc, hi, lo))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("length", (1, 128, 300, 1000))
def test_first_above_matches_searchsorted(length):
    """The blocked search over a running pair count gives
    ``searchsorted(side="right")``, across block ends, on repeated
    values and past the last entry."""
    rng = np.random.default_rng(length)
    cum = np.cumsum(rng.integers(0, 3, length)).astype(np.int32)
    x = np.arange(int(cum[-1]) + 3, dtype=np.int32)
    got = rrr_mod._first_above(jnp.asarray(cum), jnp.asarray(x))
    np.testing.assert_array_equal(
        np.minimum(np.asarray(got), length),
        np.searchsorted(cum, x, side="right"))
