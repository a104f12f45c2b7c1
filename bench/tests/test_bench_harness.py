"""The harness on the CPU at a tiny size: loading by name, the result
line, the end-to-end arithmetic, the peaks table and whole runs of the
tiny cells (the look for a chip skipped)."""
import json
import math
import os
import time

import numpy as np
import pytest

from bench_tiny import tiny_root  # noqa: F401
from bench import run
from bench.window import Item, Window

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    c = run.Cell(BENCH, cell)
    run.load_module("drivers", c.traffic["driver"])
    assert c.config["name"] == c.entry["config"]
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_layer_metric_reader_found(metric):
    assert callable(run.load_module("layer_metrics", metric).read)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    if m["name"] != "setup_s"])
def test_end_to_end_reader_found(metric):
    assert callable(run.load_module("end_to_end", metric).read)


def test_benchmark_json_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for n in names:
            assert set(n) <= NAME_OK and len(n) <= 64
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        moved = {e["name"] for e in BENCH["end_to_end"]}
        assert m["moves"] in moved
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_unknown_workload_refused():
    with pytest.raises(KeyError):
        run.Cell(BENCH, "no_such.cell")


def test_peaks_refuse_unknown_device():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")


def _window(items, t0, t1):
    w = Window(unit="rounds", t0=t0, t1=t1)
    w.items = items
    return w


def test_rate_over_whole_rounds():
    rate = run.load_module("end_to_end", "rrr_sets_per_s").read
    w = _window([Item(0.0, 31.0, 1, 16384), Item(31.0, 62.5, 1, 16384)],
                0.0, 62.5)
    assert rate(w) == pytest.approx(2 * 16384 / 62.5)


def test_query_rate_and_p95_over_every_query():
    qps = run.load_module("end_to_end", "queries_per_s").read
    p95 = run.load_module("end_to_end", "query_p95_ms").read
    items = [Item(i, i + 1, 8, 8, [0.5 + 0.01 * i] * 8)
             for i in range(20)]
    w = _window(items, 0.0, 20.0)
    assert qps(w) == pytest.approx(160 / 20.0)
    lat = sorted(x for it in items for x in it.latencies)
    assert p95(w) == pytest.approx(1000 * lat[math.ceil(0.95 * 160) - 1])
    assert p95(w) == pytest.approx(1000 * (0.5 + 0.01 * 18))


def _run(tiny_root, name, trace=False):
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cell = run.Cell(bench, name, root=tiny_root)
    return run.run_cell(cell, seed=2 ** 31 + 17, seconds=0.2, trace=trace,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["tiny_ic.round", "tiny_lt.round",
                                  "tiny_ic.serve"])
def test_tiny_cell_last_line(tiny_root, name):
    res = _run(tiny_root, name)
    line = json.dumps(res, allow_nan=False)
    assert json.loads(line) == res
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert "setup_s" in res["metrics"]
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(res["device"])


def test_compile_counter_sees_a_compile_in_the_window():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3)).block_until_ready()
    with run.CompileCounter(jax) as warm:
        f(jnp.ones(3)).block_until_ready()
    with run.CompileCounter(jax) as cold:
        f(jnp.ones(5)).block_until_ready()
    assert warm.count == 0 and cold.count >= 1


def test_batches_follow_the_replay_trace():
    """The serve mix draws its queries as ``serve.make_trace`` does."""
    from bench import gen
    from repro.core.service import Query
    from repro.launch.serve import make_trace
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                          "serve.json")))
    traffic = dict(traffic, batches=5)
    seed, n = 2 ** 31 + 9, 317080
    got = [q for b in gen.batches(traffic, n, seed, Query) for q in b]
    want = make_trace(n, 5 * traffic["batch"], seed,
                      k_max=traffic["k_max"],
                      excl_max=traffic["excluded_max"],
                      budget_frac=traffic["budget_frac"])
    assert got == want


def test_gnm_undirected_edges():
    from bench import gen
    src, dst = gen.edge_list({"generator": "gnm_undirected", "n": 1000,
                              "edges": 3000, "structure_seed": 4})
    assert src.size == dst.size == 6000 and not np.any(src == dst)
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert len(pairs) == 6000
    assert all((v, u) in pairs for u, v in pairs)


def test_program_memory_counts_temporaries():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(jnp.outer(x, x).ravel())[:4])
    with run.ProgramMemory() as programs:
        f(jnp.ones(300)).block_until_ready()
    largest, parts = programs.largest
    assert parts["temporaries"] > 0
    assert largest >= 300 * 300 * 4
    block = run.device_block(jax, programs)
    assert block["memory_peak_bytes"] >= largest


def test_setup_s_leaves_out_the_key_search(tiny_root, monkeypatch):
    from bench.find import load_module
    real = load_module("drivers", "round")

    def slow(*a, **kw):
        time.sleep(2.0)
        return pick(*a, **kw)

    pick = real.pick_keys
    monkeypatch.setattr(real, "pick_keys", slow)
    monkeypatch.setattr(run, "load_module",
                        lambda kind, name: real if kind == "drivers"
                        else load_module(kind, name))
    t0 = time.perf_counter()
    res = _run(tiny_root, "tiny_ic.round")
    assert res["correct"]
    assert res["metrics"]["setup_s"]["value"] < (time.perf_counter() - t0
                                                 - 2.0)
