"""The round's frontier counters: ``frontier_steps`` and ``coins_drawn``
of a tiny round against its ``bfs_steps`` and ``rrr_pairs``, and the two
readers that read them, from the harness's context and off the driver
state of its ``run_cell``."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_tiny import tiny_root  # noqa: F401
from bench import gen
from bench.find import load_module
from bench.reference import graph as rg, prng
from bench.reference import round as rround

N = 512
SEED = 2 ** 31 + 99
READERS = ("s1_frontier_step_share", "s1_coins_drawn_per_pair")


def _read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("sampler", ["packed", "dense"])
def test_round_frontier_counters(sampler):
    """Under IC the packed sampler's steps all fit the frontier path on
    this graph, and each sampled pair drew its ``max_degree`` coins
    once; the dense sampler draws the whole plane every step."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core import greediris
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    from repro.launch.mesh import make_im_mesh
    src, dst = gen.edge_list({"generator": "gnm_undirected", "n": N,
                              "edges": 4 * N, "structure_seed": 0})
    g = from_edge_list(src, dst, N, seed=SEED)
    d = g.max_in_degree()
    mesh = make_im_mesh(1)
    fn, _, theta = greediris.build_round(
        mesh, ("machines",), n=N, theta=256, k=8, max_degree=d, model="IC",
        sampler=sampler, fwd=padded_forward_adjacency(g), sample_chunks=2,
        solver="lazy", max_steps=32)
    rep = NamedSharding(mesh, P())
    kd = prng.key_from_seed(1000)
    out = jax.jit(fn)(*[jax.device_put(a, rep) for a in padded_adjacency(g)],
                      jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32)))
    tab = rg.tables(src, dst, N, SEED)
    _, _, steps = rround.sample_round(tab, kd, theta=theta, chunks=2,
                                      model="IC", max_steps=32,
                                      cumw=rg.lt_thresholds(tab.wt))
    assert int(out.bfs_steps) == sum(steps) and max(steps) < 32
    if sampler == "packed":
        assert int(out.frontier_steps) == int(out.bfs_steps)
        assert float(out.coins_drawn) == d * int(out.rrr_pairs)
    else:
        assert int(out.frontier_steps) == 0
        assert float(out.coins_drawn) == pytest.approx(
            int(out.bfs_steps) * fn.units.coins_per_bfs_step)


def test_readers_from_context():
    ctx = {"counters": dict(bfs_steps=40, frontier_steps=30,
                            coins_drawn=21000.0, rrr_pairs=700)}
    assert _read("s1_frontier_step_share", ctx) == pytest.approx(75.0)
    assert _read("s1_coins_drawn_per_pair", ctx) == pytest.approx(30.0)
    # a harness that passes the older counters only, or none
    for counters in ({}, dict(bfs_steps=40, rrr_pairs=700)):
        for name in READERS:
            assert _read(name, {"counters": counters}) is None


def _out(steps, front, coins, pairs):
    return SimpleNamespace(seeds=None, bfs_steps=steps, rrr_pairs=pairs,
                           sender_tiles_swept=1, frontier_steps=front,
                           coins_drawn=coins)


def test_readers_off_the_driver_state():
    """Called by the harness's ``run_cell``, the readers sum the
    window's outputs; outputs of a program that does not count frontier
    steps, and no harness, read as nothing."""
    def run_cell(state, cell, name):
        return _read(name, {})

    state = SimpleNamespace(outputs=[(0, _out(33, 33, 1.0e5, 6000)),
                                     (1, _out(31, 30, 3.0e5, 6000))])
    cell = SimpleNamespace(chips=1)
    assert run_cell(state, cell, "s1_frontier_step_share") == pytest.approx(
        100.0 * 63 / 64)
    assert run_cell(state, cell, "s1_coins_drawn_per_pair") == pytest.approx(
        4.0e5 / 12000)
    state.outputs = [(0, SimpleNamespace(seeds=None, bfs_steps=33,
                                         rrr_pairs=6000,
                                         sender_tiles_swept=1))]
    for name in READERS:
        assert run_cell(state, cell, name) is None
        assert _read(name, {}) is None


def test_tiny_traced_round_reads_frontier_counters(tiny_root, monkeypatch):
    """A traced tiny round on the CPU: both counters reach their
    readers through the unchanged harness."""
    import json
    import os
    from bench import run
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cell = run.Cell(bench, "tiny_ic.round", root=tiny_root)
    res = run.run_cell(cell, seed=2 ** 31 + 23, seconds=0.2, trace=True,
                       t_start=time.perf_counter())
    assert res["correct"]
    assert res["metrics"]["s1_frontier_step_share"]["value"] == 100.0
    per_pair = res["metrics"]["s1_coins_drawn_per_pair"]["value"]
    assert 0 < per_pair < np.inf
