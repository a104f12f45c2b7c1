"""Input generation from ``--seed``: the graph's edge list and the
service's query stream.  Reads only the parameters of the
configuration and the traffic mix.

The graph's edges are fixed by the configuration (the round program
bakes its forward adjacency in as a constant, so one graph means one
compiled program for every seed); the seed draws its edge
probabilities and LT weights (through the program's
``from_edge_list(seed=...)``) and every query of the service's
batches.
"""
from __future__ import annotations

import numpy as np


def edge_list(graph: dict):
    """Directed edges (src, dst) of the configuration's graph: ``edges``
    distinct undirected pairs {u, v}, u != v, drawn uniformly (G(n, m))
    from ``structure_seed``, each taken in both directions."""
    if graph["generator"] != "gnm_undirected":
        raise ValueError(f"unknown graph generator {graph['generator']!r}")
    n, m = int(graph["n"]), int(graph["edges"])
    if m > n * (n - 1) // 2:
        raise ValueError(f"{m} edges do not fit {n} vertices")
    rng = np.random.default_rng(graph["structure_seed"])
    pairs = np.empty(0, np.int64)
    while pairs.size < m:
        u = rng.integers(0, n, size=2 * (m - pairs.size) + 16)
        v = rng.integers(0, n, size=u.size)
        ok = u != v
        cand = np.minimum(u, v)[ok] * n + np.maximum(u, v)[ok]
        both = np.concatenate([pairs, cand])
        _, first = np.unique(both, return_index=True)
        pairs = both[np.sort(first)]
    pairs = pairs[:m]
    lo, hi = pairs // n, pairs % n
    return np.concatenate([lo, hi]), np.concatenate([hi, lo])


def batches(traffic: dict, n: int, seed: int, query_type) -> list:
    """The run's batches, drawn from ``seed`` query by query as the
    service's replay trace draws them (``launch/serve.make_trace``): k
    uniform on [1, k_max], 0 to ``excluded_max`` excluded vertices,
    and a spread budget uniform on [1, budget_frac * n] for a
    ``budget_share`` of the queries."""
    rng = np.random.default_rng(seed)
    size = traffic["batch"]
    out = []
    for _ in range(traffic["batches"]):
        batch = []
        for _ in range(size):
            k = int(rng.integers(1, traffic["k_max"] + 1))
            e = int(rng.integers(0, traffic["excluded_max"] + 1))
            excluded = tuple(int(v) for v in
                             rng.choice(n, size=e, replace=False)) if e else ()
            budget = (float(rng.uniform(1.0, traffic["budget_frac"] * n))
                      if rng.random() < traffic["budget_share"] else None)
            batch.append(query_type(k=k, excluded=excluded, budget=budget))
        out.append(batch)
    return out


def shape_of(batch) -> tuple:
    """(max k, exclusion width) — the static shape a batch compiles."""
    return (max(q.k for q in batch),
            max(1, max(len(q.excluded) for q in batch)))
