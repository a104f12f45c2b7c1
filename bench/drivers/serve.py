"""Driver ``serve``: the query service under a closed loop.

Set-up builds the configuration's graph and an ``InfluenceService``
on the key ``fold_in(key(seed), 0)``, whose resident pool is filled
once (``refresh``) and then never changes.  The run's batches are drawn
from the seed (``gen.batches``), and set-up answers one batch of every
(max k, exclusion width) shape among them: the service compiles its
solve for each such shape, so the window compiles nothing and the
compiles a user of this mix pays show in ``setup_s``.  In the window
one client sends a batch, waits for its answers, and sends the next,
in the drawn order: each batch through ``admit`` and ``answer``.  A
query's latency runs from its batch's submission to the moment its
``Answer`` is on the host.  The window ends with the batch that
crosses ``--seconds``.  After the window the reference samples the same
pool on the host and answers every query again; the check compares
seeds, coverage, k used, the certificate's bounds and its verdict.
"""
from __future__ import annotations

import sys
import time
from types import SimpleNamespace

import numpy as np

from bench import gen
from bench.reference import graph as ref_graph, prng
from bench.reference import service as ref_service
from bench.window import Item, Window

LIMITS = {"answer_mismatch": 0, "coverage_gap": 0, "bound_gap": 0.0}


class State:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.answered = []

    def describe(self) -> str:
        return (f"graph n={self.n} edges={self.edges}; pool theta="
                f"{self.theta} per half, filled in {self.fill_s:.2f}s; "
                f"{len(self.batches)} batches drawn; warmed "
                f"{len(self.warmed)} shapes (max k, exclusion width) "
                f"{sorted(self.warmed)} in {self.warm_s:.2f}s")

    def shapes(self) -> dict:
        return {"n_pad": self.n, "words": self.theta // 32}


def service_key(seed: int):
    """The service's key as the reference's key pair."""
    return prng.fold_in(prng.key_from_seed(seed), 0)


def reference_pool(cfg: dict, src, dst, seed: int, coin: str = "f32"):
    """The service's pool as the reference samples it: two halves and
    their BFS steps."""
    svc = cfg["service"]
    n = int(cfg["graph"]["n"])
    tab = ref_graph.tables(src, dst, n, seed)
    return ref_service.sample_pool(
        tab, service_key(seed), theta=svc["theta"], slab=svc["slab"],
        model=cfg["model"], max_steps=cfg["max_steps"],
        coin_chunk=cfg["coin_chunk"], cumw=ref_graph.lt_thresholds(tab.wt),
        coin=coin)


def setup(cell, *, seed: int) -> State:
    import jax
    import jax.numpy as jnp

    from repro.core.service import InfluenceService, Query
    from repro.graphs.csr import from_edge_list

    cfg, tr = cell.config, cell.traffic
    svc = cfg["service"]
    n = int(cfg["graph"]["n"])
    src, dst = gen.edge_list(cfg["graph"])
    g = from_edge_list(src, dst, n, seed=seed)
    service = InfluenceService(
        g, jax.random.wrap_key_data(jnp.asarray(service_key(seed),
                                                jnp.uint32)),
        theta0=svc["theta"], max_theta=svc["theta"], slab=svc["slab"],
        solver=svc["solver"], model=cfg["model"], sampler=svc["sampler"],
        coin_chunk=cfg["coin_chunk"], max_steps=cfg["max_steps"])
    t0 = time.perf_counter()
    service.refresh()
    jax.block_until_ready((service.pool.r1, service.pool.r2))
    fill_s = time.perf_counter() - t0

    batches = gen.batches(tr, n, seed, Query)
    warmed = {}
    for batch in batches:
        warmed.setdefault(gen.shape_of(batch), batch)
    t0 = time.perf_counter()
    for batch in warmed.values():
        service.answer([service.admit(q) for q in batch])
    warm_s = time.perf_counter() - t0
    return State(service=service, cfg=cfg, seed=seed, n=n, src=src, dst=dst,
                 edges=g.num_edges, batches=batches,
                 theta=service.pool.theta, fill_s=fill_s, warm_s=warm_s,
                 warmed=set(warmed), harness_s=0.0, pool=None)


def window(state: State, seconds: float) -> Window:
    import jax

    service = state.service
    w = Window(unit="batches")
    w.t0 = time.perf_counter()
    i = 0
    while True:
        batch = state.batches[i % len(state.batches)]
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("admit"):
            tickets = [service.admit(q) for q in batch]
        with jax.profiler.TraceAnnotation("answer"):
            answers = service.answer(tickets)
        end = time.perf_counter()
        with jax.profiler.TraceAnnotation("epilogue"):
            w.items.append(Item(t, end, len(batch), len(batch),
                                [end - t] * len(batch)))
            state.answered.append((batch, answers))
        i += 1
        if end - w.t0 >= seconds:
            break
    w.t1 = end
    if i > len(state.batches):
        print(f"[bench] the window sent {i} batches and went round the "
              f"{len(state.batches)} drawn", file=sys.stderr, flush=True)
    return w


def release(state: State):
    del state.service


def _rel(a: float, b: float) -> float:
    """Gap of a bound (in vertices) or a ratio, relative to the
    reference's value where that is above 1."""
    return abs(a - b) / max(abs(b), 1.0)


def check(state: State, window: Window):
    """Every answer of the window against the reference's pool of the
    service's key, sampled on the host now."""
    if state.pool is None:
        state.pool = reference_pool(state.cfg, state.src, state.dst,
                                    state.seed)
    r1, r2, _ = state.pool
    k_max = max(q.k for batch, _ in state.answered for q in batch)
    ref = ref_service.Answerer(r1, r2, k_max=k_max)
    mismatch = failed = 0
    cov_gap, bound_gap = 0, 0.0
    for batch, answers in state.answered:
        for q, a in zip(batch, answers):
            r = ref.answer(q.k, q.excluded, q.budget, q.eps)
            wrong = not (np.array_equal(a.seeds, r.seeds)
                         and a.k_used == r.k_used
                         and a.certified == r.certified)
            gap = abs(a.coverage - r.coverage)
            bgap = max(_rel(a.sigma_lower, r.sigma_lower),
                       _rel(a.sigma_upper, r.sigma_upper),
                       _rel(a.guarantee, r.guarantee))
            mismatch += wrong
            cov_gap = max(cov_gap, gap)
            bound_gap = max(bound_gap, bgap)
            failed += bool(wrong or gap or bgap > LIMITS["bound_gap"])
    print(f"[bench] reference: {sum(len(b) for b, _ in state.answered)} "
          f"answers checked; pool BFS steps {state.pool[2]}; pool "
          f"coverage of the first k={k_max} picks {int(ref.gains.sum())}",
          file=sys.stderr, flush=True)
    values = {"answer_mismatch": mismatch, "coverage_gap": cov_gap,
              "bound_gap": bound_gap}
    return ({k: {"value": v, "limit": LIMITS[k]} for k, v in
             values.items()}, failed)


def control_state(cell, seed: int, coin: str):
    """This driver's state after a window, with every drawn batch
    answered by the reference from the service key's pool drawn with
    ``coin``s (the control in the program's place)."""
    from repro.core.service import Query
    cfg = cell.config
    src, dst = gen.edge_list(cfg["graph"])
    n = int(cfg["graph"]["n"])
    r1, r2, _ = reference_pool(cfg, src, dst, seed, coin)
    batches = gen.batches(cell.traffic, n, seed, Query)
    ans = ref_service.Answerer(r1, r2,
                               k_max=max(q.k for b in batches for q in b))
    answered = [(b, [ans.answer(q.k, q.excluded, q.budget, q.eps)
                     for q in b]) for b in batches]
    return SimpleNamespace(answered=answered, cfg=cfg, seed=seed, src=src,
                           dst=dst, pool=None)
