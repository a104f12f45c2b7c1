"""Driver ``round``: back-to-back fixed-theta GreediRIS rounds.

Set-up builds the configuration's graph, the round program
(``greediris.build_round`` on a ``make_im_mesh(chips)`` mesh, as
``im_driver --theta`` calls it) and compiles it, then picks the
window's round keys.  The window runs rounds, each on a fresh key,
until ``--seconds`` have passed; a round is timed from its call until
its outputs are ready.  The check compares every round's seeds and
coverages with the reference round on the same key.

Round keys: candidate j is ``fold_in(key(seed), j)``.  The time of a
round is its number of BFS steps times a fixed cost per step, and the
steps vary from key to key by some 10% under IC.  So where the
configuration states ``bfs_steps_per_round``, only candidates whose
sampling takes exactly that many steps (counted by the reference) are
kept: every seed then gets rounds of the same size.  That search and
the reference's tables are the harness's own work, timed apart
(``harness_s``) and left out of ``setup_s``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from types import SimpleNamespace

from bench import gen
from bench.reference import cover, graph as ref_graph, prng
from bench.reference import round as ref_round
from bench.window import Item, Window

LIMITS = {"seed_mismatch": 0, "coverage_gap": 0, "seed_cover_gap": 0}


class State:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.outputs = []

    def describe(self) -> str:
        return (f"graph n={self.n} edges={self.edges} "
                f"max_in_degree={self.d_max}; {self.model} theta="
                f"{self.theta} k={self.k}; compile {self.compile_s:.2f}s; "
                f"{len(self.keys)} round keys from {self.tried} "
                f"candidates, BFS steps per chunk {self.steps} (key search "
                f"and reference tables {self.harness_s:.2f}s, not set-up)")

    def shapes(self) -> dict:
        return {"n_pad": self.n_pad, "words": self.theta // 32}


def pick_keys(tab, cfg, traffic, seed: int, theta: int):
    """The window's round keys, each with its reference sets, and the
    number of candidates tried."""
    kw = dict(theta=theta, chunks=cfg["sample_chunks"], model=cfg["model"],
              max_steps=cfg["max_steps"], coin_chunk=cfg["coin_chunk"],
              cumw=ref_graph.lt_thresholds(tab.wt))
    target = cfg.get("bfs_steps_per_round")
    kept, tried = [], 0
    while len(kept) < traffic["rounds_prepared"]:
        if tried >= traffic["max_candidates"]:
            raise RuntimeError(f"{tried} candidate keys gave no round "
                               f"of {target} BFS steps")
        kd = prng.fold_in(prng.key_from_seed(seed), tried)
        tried += 1
        sets = ref_round.sample_round(tab, kd, **kw)
        if target is None or sum(sets[2]) == target:
            kept.append((kd, sets))
    return kept, tried


def setup(cell, *, seed: int) -> State:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import greediris
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    from repro.launch.mesh import make_im_mesh

    cfg, tr = cell.config, cell.traffic
    n = int(cfg["graph"]["n"])
    src, dst = gen.edge_list(cfg["graph"])
    g = from_edge_list(src, dst, n, seed=seed)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    theta = int(cfg["theta_per_chip"]) * cell.chips
    mesh = make_im_mesh(cell.chips)
    fn, n_pad, _ = greediris.build_round(
        mesh, ("machines",), n=n, theta=theta, k=cfg["k"],
        max_degree=g.max_in_degree(), model=cfg["model"],
        delta=cfg["delta"], aggregate=cfg["aggregate"],
        max_steps=cfg["max_steps"], sample_chunks=cfg["sample_chunks"],
        use_kernel=cfg["receiver_kernel"], shuffle=cfg["shuffle"],
        solver=cfg["solver"], sampler=cfg["sampler"], fwd=fwd,
        coin_chunk=cfg["coin_chunk"])
    rep = NamedSharding(mesh, P())
    arrays = [jax.device_put(a, rep) for a in (nbr, prob, wt)]

    t0 = time.perf_counter()
    tab = ref_graph.tables(src, dst, n, seed)
    if int((tab.nbr >= 0).sum()) != g.num_edges:
        raise AssertionError("the reference tables lost edges")
    kept, tried = pick_keys(tab, cfg, tr, seed, theta)
    harness_s = time.perf_counter() - t0
    keys = [jax.device_put(jax.random.wrap_key_data(
        jnp.asarray(kd, jnp.uint32)), rep) for kd, _ in kept]

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*arrays, keys[0]).compile()
    compile_s = time.perf_counter() - t0
    return State(compiled=compiled, arrays=arrays, keys=keys, kept=kept,
                 tab=tab, cfg=cfg, n=n, n_pad=n_pad, edges=g.num_edges,
                 d_max=g.max_in_degree(), model=cfg["model"], theta=theta,
                 k=cfg["k"], compile_s=compile_s, tried=tried,
                 harness_s=harness_s,
                 steps=[sets[2] for _, sets in kept], results=[])


def window(state: State, seconds: float) -> Window:
    import jax

    w = Window(unit="rounds")
    w.t0 = time.perf_counter()
    i = 0
    while True:
        key = i % len(state.keys)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("round"):
            out = state.compiled(*state.arrays, state.keys[key])
            jax.block_until_ready(out)
        end = time.perf_counter()
        with jax.profiler.TraceAnnotation("epilogue"):
            w.items.append(Item(t, end, 1, state.theta))
            state.outputs.append((key, out))
        i += 1
        if end - w.t0 >= seconds:
            break
    w.t1 = end
    return w


def release(state: State):
    """Outputs to the host; the program and its inputs freed."""
    state.results = [(key, np.asarray(o.seeds), int(o.coverage),
                      int(o.global_coverage), int(o.best_local_coverage))
                     for key, o in state.outputs]
    state.outputs = []
    del state.compiled, state.arrays, state.keys


def check(state: State, window: Window):
    cfg = state.cfg
    refs = {}
    seed_mismatch = coverage_gap = seed_cover_gap = failed = 0
    for key, seeds, cov, gcov, lcov in state.results:
        kd, (samples, verts, _) = state.kept[key]
        if key not in refs:
            refs[key] = (
                ref_round.solve_round(state.tab, kd, samples, verts,
                                      theta=state.theta, k=state.k,
                                      delta=cfg["delta"]),
                cover.Incidence(verts, samples, state.n, state.theta))
        ref, inc = refs[key]
        mism = int(np.sum(seeds != ref.seeds))
        gap = max(abs(cov - ref.coverage), abs(gcov - ref.global_coverage),
                  abs(lcov - ref.best_local_coverage))
        cgap = abs(inc.coverage(seeds) - cov)
        seed_mismatch += mism
        coverage_gap = max(coverage_gap, gap)
        seed_cover_gap = max(seed_cover_gap, cgap)
        failed += bool(mism or gap or cgap)
    near = sum(r.near_ties for r, _ in refs.values())
    print(f"[bench] reference: {len(refs)} distinct rounds checked, "
          f"{len(state.results)} outputs; coverage "
          f"{[r.coverage for r, _ in refs.values()]}; receiver "
          f"decisions within 1e-5 of a threshold: {near}", flush=True,
          file=sys.stderr)
    values = {"seed_mismatch": seed_mismatch, "coverage_gap": coverage_gap,
              "seed_cover_gap": seed_cover_gap}
    return ({k: {"value": v, "limit": LIMITS[k]} for k, v in
             values.items()}, failed)


def control_state(cell, seed: int, coin: str):
    """This driver's state after a window of one round, its output
    computed by the reference drawing ``coin``s (the control in the
    program's place)."""
    cfg = cell.config
    n = int(cfg["graph"]["n"])
    src, dst = gen.edge_list(cfg["graph"])
    tab = ref_graph.tables(src, dst, n, seed)
    theta = int(cfg["theta_per_chip"]) * cell.chips
    kept, _ = pick_keys(tab, cfg, cell.traffic, seed, theta)
    kd = kept[0][0]
    samples, verts, _ = ref_round.sample_round(
        tab, kd, theta=theta, chunks=cfg["sample_chunks"],
        model=cfg["model"], max_steps=cfg["max_steps"],
        coin_chunk=cfg["coin_chunk"], cumw=ref_graph.lt_thresholds(tab.wt),
        coin=coin)
    o = ref_round.solve_round(tab, kd, samples, verts, theta=theta,
                              k=cfg["k"], delta=cfg["delta"])
    return SimpleNamespace(
        results=[(0, o.seeds, o.coverage, o.global_coverage,
                  o.best_local_coverage)],
        kept=kept, tab=tab, cfg=cfg, theta=theta, k=cfg["k"], n=n)
