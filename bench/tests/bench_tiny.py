"""Shared by the benchmark's tests: the repository root and ``src`` on
``sys.path``, and a checkout-like directory of tiny cells.  (Not a
``conftest.py``: the repository's own ``tests/conftest.py`` is imported
by name, and a second module of that name would shadow it.)"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_IC = {
    "name": "tiny_ic", "graph": {"generator": "gnm_undirected", "n": 512,
                                 "edges": 2048, "structure_seed": 0},
    "model": "IC", "k": 8, "theta_per_chip": 256, "sample_chunks": 2,
    "max_steps": 32, "coin_chunk": 32, "delta": 0.077,
    "sampler": "packed", "solver": "lazy", "receiver_kernel": True,
    "aggregate": "gather", "shuffle": "dense", "bfs_steps_per_round": None,
    "service": {"theta": 256, "slab": 128, "solver": "resident",
                "sampler": "packed"}}
TINY_LT = dict(TINY_IC, name="tiny_lt", model="LT", bfs_steps_per_round=64)
TINY_SERVE = {
    "driver": "serve", "batch": 4, "batches": 6, "k_max": 8,
    "excluded_max": 3, "budget_share": 0.3, "budget_frac": 0.25}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-like directory holding a BENCHMARK.json of tiny cells
    that reuse the real drivers and metric readers."""
    root = tmp_path_factory.mktemp("tiny")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    os.makedirs(root / "bench" / "configs")
    os.makedirs(root / "bench" / "traffic")
    for cfg in (TINY_IC, TINY_LT):
        json.dump(cfg, open(root / "bench" / "configs" /
                            f"{cfg['name']}.json", "w"))
    rnd = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      "round.json")))
    json.dump(rnd, open(root / "bench" / "traffic" / "round.json", "w"))
    json.dump(TINY_SERVE, open(root / "bench" / "traffic" / "serve.json",
                               "w"))
    cells = {"tiny_ic.round": ("tiny_ic", "round"),
             "tiny_lt.round": ("tiny_lt", "round"),
             "tiny_ic.serve": ("tiny_ic", "serve")}
    real_of = {"tiny_ic.round": "er18_ic.round",
               "tiny_lt.round": "er18_lt.round",
               "tiny_ic.serve": "er18_ic.serve"}

    def rename(ws):
        inv = {v: k for k, v in real_of.items()}
        return [inv[w] for w in ws]

    bench = {
        "configs": [{"name": c["name"],
                     "file": f"bench/configs/{c['name']}.json"}
                    for c in (TINY_IC, TINY_LT)],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1}
                      for n, (c, t) in cells.items()],
        "end_to_end": [dict(m, **({"workloads": rename(m["workloads"])}
                                  if "workloads" in m else {}))
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=rename(m["workloads"]))
                      for m in real["per_layer"]]}
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return str(root)
