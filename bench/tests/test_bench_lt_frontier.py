"""LT under the frontier step: the packed engines against the plain
reference on the cases LT's draw has to get right, the running weight
sums against the reference's at ``er18_lt``'s own tables, and the
round's ``frontier_pairs`` counter with its reader
``s1_frontier_fill``."""
import functools
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_tiny import ROOT, tiny_root  # noqa: F401
from bench import gen
from bench.find import load_module
from bench.reference import graph as rg, prng, rrr
from bench.reference import round as rround

N = 512
SEED = 2 ** 31 + 99


def jkey(kd):
    return jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32))


def _fill(ctx):
    return load_module("layer_metrics", "s1_frontier_fill").read(ctx)


def test_running_sums_match_reference_at_er18_lt():
    """The program's LT thresholds are the reference's, bit for bit, on
    the cell's graph and weights."""
    from repro.core.rrr import _running_sums
    from repro.graphs.csr import from_edge_list, padded_adjacency
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "er18_lt.json")))
    n = cfg["graph"]["n"]
    src, dst = gen.edge_list(cfg["graph"])
    seed = 2 ** 31 + 5
    wt = padded_adjacency(from_edge_list(src, dst, n, seed=seed))[2]
    tab = rg.tables(src, dst, n, seed)
    assert np.array_equal(np.asarray(wt), tab.wt)
    got = np.asarray(jax.jit(_running_sums)(wt))
    assert np.array_equal(got, rg.lt_thresholds(tab.wt))


def _tables(src, dst, n, scale=1.0):
    """The program's padded tables and forward adjacency of an edge
    list, with the reference's tables holding the same arrays."""
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    g = from_edge_list(np.asarray(src), np.asarray(dst), n, seed=SEED)
    nbr, prob, wt = (np.asarray(a) for a in padded_adjacency(g))
    wt = wt * np.float32(scale)
    return (rg.Tables(nbr, prob, wt, np.sum(nbr >= 0, axis=1)),
            padded_forward_adjacency(g))


def _chain(n):
    return np.arange(n - 1), np.arange(1, n), n


def _case(name, kb):
    """(tables, forward adjacency, roots) of one case."""
    deep_src, deep_dst = gen.edge_list({"generator": "gnm_undirected",
                                        "n": 60, "edges": 180,
                                        "structure_seed": 9})
    if name == "deep_chain":          # 47 steps, past 32
        return _tables(*_chain(48)) + (np.full(32, 47),)
    if name in ("batch_40", "batch_72"):
        b = int(name[-2:])
        return _tables(deep_src, deep_dst, 60) + (
            prng.randint(prng.fold_in(kb, 1), b, 0, 60),)
    if name == "in_degree_0":         # vertices 0-9 have no in-edge
        src = [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 5, 9, 2]
        dst = [10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 11, 12, 12, 19]
        return _tables(src, dst, 20) + (
            prng.randint(prng.fold_in(kb, 1), 64, 0, 20),)
    if name == "short_rows":          # in-weights sum to 0.6
        return _tables(deep_src, deep_dst, 60, 0.6) + (
            prng.randint(prng.fold_in(kb, 1), 64, 0, 60),)
    # draw_on_a_sum: vertex 0's first running sum is exactly sample 0's
    # first draw there, so it follows its second in-edge, to vertex 2
    tab, fwd = _tables([1, 2, 3], [0, 0, 1], 4)
    _, sub = prng.split(kb)
    r = prng.uniform_f32(prng.bits32(sub, np.array([0])))[0]
    tab.wt[0, :2] = (r, np.float32(1.0) - r)
    return tab, fwd, np.zeros(32)


@pytest.mark.parametrize("kernel", (False, True))
@pytest.mark.parametrize("name", ("deep_chain", "batch_40", "batch_72",
                                  "in_degree_0", "short_rows",
                                  "draw_on_a_sum"))
def test_lt_frontier_step_matches_reference(name, kernel):
    from repro.core import bitset
    from repro.core.rrr import _rrr_batch_packed
    kb = prng.key_from_seed(SEED)
    tab, fwd, roots = _case(name, kb)
    b, v, steps = rrr.sample_batch(tab, roots, kb, model="LT",
                                   max_steps=64,
                                   cumw=rg.lt_thresholds(tab.wt))
    want = np.zeros((len(roots), tab.n), bool)
    want[b, v] = True
    x, count = jax.jit(functools.partial(
        _rrr_batch_packed, model="LT", max_steps=64, coin_chunk=32,
        kernel=kernel))(*map(jnp.asarray, (tab.nbr, tab.prob, tab.wt)),
                        *fwd, jnp.asarray(roots, jnp.int32), jkey(kb))
    got = np.asarray(bitset.unpack_words(x, len(roots))).T
    assert np.array_equal(got, want)
    assert int(count.bfs_steps) == steps == int(count.frontier_steps)
    if name == "deep_chain":
        assert steps == 48
    if name == "short_rows":
        sizes = want.sum(axis=1)
        assert sizes.min() < sizes.max()
    if name == "draw_on_a_sum":
        assert list(want[0]) == [True, False, True, False]


def _tiny_round(model):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core import greediris
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    from repro.launch.mesh import make_im_mesh
    src, dst = gen.edge_list({"generator": "gnm_undirected", "n": N,
                              "edges": 4 * N, "structure_seed": 0})
    g = from_edge_list(src, dst, N, seed=SEED)
    mesh = make_im_mesh(1)
    fn, _, theta = greediris.build_round(
        mesh, ("machines",), n=N, theta=256, k=8,
        max_degree=g.max_in_degree(), model=model, sampler="packed",
        fwd=padded_forward_adjacency(g), sample_chunks=2, solver="lazy",
        max_steps=256)
    rep = NamedSharding(mesh, P())
    kd = prng.key_from_seed(1000)
    out = jax.jit(fn)(*[jax.device_put(a, rep) for a in padded_adjacency(g)],
                      jkey(kd))
    tab = rg.tables(src, dst, N, SEED)
    _, _, steps = rround.sample_round(tab, kd, theta=theta, chunks=2,
                                      model=model, max_steps=256,
                                      cumw=rg.lt_thresholds(tab.wt))
    return fn, out, steps


def test_lt_round_counts_frontier_pairs():
    """Under LT every step of the round is a frontier step, each pair
    drew one uniform, and every sampled pair was a frontier pair once;
    the fill is those pairs over the steps' capacity of one batch."""
    fn, out, steps = _tiny_round("LT")
    assert int(out.bfs_steps) == sum(steps)
    assert int(out.frontier_steps) == int(out.bfs_steps)
    assert int(out.frontier_pairs) == int(out.rrr_pairs)
    assert float(out.coins_drawn) == int(out.frontier_pairs)
    assert fn.frontier_capacity == 128
    counters = dict({f: float(getattr(out, f)) for f in
                     ("bfs_steps", "frontier_steps", "frontier_pairs",
                      "rrr_pairs", "coins_drawn")},
                    **fn.units._asdict(),
                    frontier_capacity=fn.frontier_capacity)
    want = 100.0 * int(out.rrr_pairs) / (int(out.bfs_steps) * 128)
    assert _fill({"counters": counters}) == pytest.approx(want)
    assert 0 < want <= 100


def test_ic_round_counts_frontier_pairs():
    """Under IC the pairs the frontier steps held are the sampled pairs
    too, and each drew ``max_degree`` coins."""
    fn, out, _ = _tiny_round("IC")
    assert int(out.frontier_steps) == int(out.bfs_steps)
    assert int(out.frontier_pairs) == int(out.rrr_pairs)
    assert float(out.coins_drawn) == (fn.units.coins_per_bfs_step // (
        128 * N)) * int(out.frontier_pairs)
    assert fn.frontier_capacity == 256


@pytest.mark.parametrize("model,machines,chunks,want",
                         [("IC", 1, 4, 8192), ("LT", 1, 4, 4096),
                          ("IC", 4, 2, 4096), ("LT", 4, 2, 2048),
                          ("LT", 1, 2, 8192)])
def test_frontier_capacity_of_a_round(model, machines, chunks, want):
    """A frontier step holds twice a chunk's batch under IC and one
    batch under LT, in whole update blocks; the units of the round are
    those they were."""
    from repro.core import greediris
    cap = greediris.frontier_capacity(n=317080, theta=16384,
                                      machines=machines, model=model,
                                      sample_chunks=chunks)
    assert cap == want
    assert greediris.RoundUnits._fields == (
        "coins_per_bfs_step", "sender_picks", "sender_tiles_per_pick")


def _out(steps, front, pairs, **extra):
    return SimpleNamespace(seeds=None, bfs_steps=steps, rrr_pairs=pairs,
                           sender_tiles_swept=1, frontier_steps=front,
                           coins_drawn=float(pairs), **extra)


def test_fill_off_the_driver_state():
    """Called by the harness's ``run_cell``, the reader sums the
    window's outputs over the program's own capacity; a parent's
    outputs, which count no frontier pairs, and no harness read as
    nothing."""
    def run_cell(state, cell):
        return _fill({})

    cfg = {"sample_chunks": 4, "coin_chunk": 32}
    state = SimpleNamespace(
        cfg=cfg, n=1000, theta=16384, k=10, d_max=21, model="LT",
        outputs=[(0, _out(200, 200, 100000, frontier_pairs=100000)),
                 (1, _out(240, 240, 130000, frontier_pairs=130000))])
    cell = SimpleNamespace(chips=1)
    assert run_cell(state, cell) == pytest.approx(
        100.0 * 230000 / (440 * 4096))
    state.model = "IC"
    assert run_cell(state, cell) == pytest.approx(
        100.0 * 230000 / (440 * 8192))
    state.outputs = [(0, _out(33, 33, 6000))]
    assert run_cell(state, cell) is None
    assert _fill({}) is None


def test_fill_silent_without_its_counters():
    given = dict(bfs_steps=40, frontier_steps=30, frontier_pairs=600,
                 rrr_pairs=700, frontier_capacity=100)
    assert _fill({"counters": given}) == pytest.approx(20.0)
    for drop in ("frontier_pairs", "frontier_capacity"):
        assert _fill({"counters": {k: v for k, v in given.items()
                                   if k != drop}}) is None
    assert _fill({"counters": dict(given, frontier_steps=0)}) is None
    assert _fill({"counters": {}}) is None


def test_tiny_traced_lt_round_reads_frontier_fill(tiny_root, monkeypatch):
    """A traced tiny LT round on the CPU: every step a frontier step,
    at most one uniform a sampled pair, and the fill read through the
    unchanged harness."""
    from bench import run
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cell = run.Cell(bench, "tiny_lt.round", root=tiny_root)
    res = run.run_cell(cell, seed=2 ** 31 + 29, seconds=0.2, trace=True,
                       t_start=time.perf_counter())
    assert res["correct"]
    m = res["metrics"]
    assert m["s1_frontier_step_share"]["value"] == 100.0
    # a chunk cut at max_steps leaves its last hits unexpanded
    assert 0.5 < m["s1_coins_drawn_per_pair"]["value"] <= 1.0
    assert 0 < m["s1_frontier_fill"]["value"] <= 100



def _mid_lt_cell(tmp_path):
    from test_bench_control import MID, _cell
    from bench import run
    traffic = json.load(open(os.path.join(run.BENCH, "traffic",
                                          "round_lt.json")))
    cfg = dict(MID, name="mid", model="LT", max_steps=256,
               bfs_steps_per_round=None)
    return _cell(tmp_path, cfg, "round_lt", traffic)


def test_round_lt_control_fails(tmp_path):
    """The control (the reference in bfloat16) reads as not correct at
    the LT cell's own step cap and traffic."""
    from bench import control
    out = control.control(_mid_lt_cell(tmp_path), seed=2 ** 31 + 7)
    assert not out["correct"]
    assert out["checks"]["seed_mismatch"]["value"] > 0


def test_round_lt_reference_in_place_passes(tmp_path):
    from bench import run
    driver = run.load_module("drivers", "round")
    state = driver.control_state(_mid_lt_cell(tmp_path), 2 ** 31 + 7, "f32")
    checks, failed = driver.check(state, None)
    assert failed == 0 and all(c["value"] == 0 for c in checks.values())
