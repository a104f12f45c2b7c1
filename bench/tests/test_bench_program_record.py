"""The readers of the program's own records: its spans, the round's
counters and the programs the device launched, and the per-layer
metrics the benchmark had before them, which read as they did."""
import json
import os
import time
from types import SimpleNamespace

import pytest

from bench_tiny import tiny_root  # noqa: F401
from bench import program_record, run
from bench import trace_reduce as tr
from bench.find import load_module
from bench.trace_reduce import Op, Reduced, Span
from bench.window import Item, Window

MS = 1_000_000
DEV = "/device:TPU:0"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_round_epilogue.xplane.pb")


def _read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def _round_trace():
    ops = [Op("fusion.1", 0 * MS, 4 * MS, DEV, "jit_round"),
           Op("lazy_greedy", 4 * MS, 5 * MS, DEV, "jit_round"),
           Op("fusion.1", 9 * MS, 12 * MS, DEV, "jit_round"),
           Op("bucket_insert_stream", 12 * MS, 13 * MS, DEV, "jit_round"),
           Op("fusion.9", 30 * MS, 40 * MS, DEV, "jit_other")]
    spans = [Span("round", 0, 6 * MS), Span("epilogue", 6 * MS, 8 * MS),
             Span("round", 8 * MS, 14 * MS)]
    return Reduced(ops, spans)


def _serve_trace():
    """Two batches of the serve loop: admit, answer and epilogue.  In
    each answer the device runs the solve (a while loop whose body runs
    three times), its epilogue, then three one-element fetches of one
    module back to back; a fetch launched in ``epilogue`` is not the
    answer's."""
    ops, spans = [], []
    for b in range(2):
        t = b * 21 * MS

        def at(ms, t=t):
            return t + int(ms * MS)
        spans += [Span("admit", at(0), at(1)), Span("answer", at(1), at(20)),
                  Span("epilogue", at(20), at(21))]
        solve = "jit__greedy_maxcover_batch"
        ops += [Op("copy.1", at(4), at(4.5), DEV, solve),
                Op("while.1", at(4.5), at(11), DEV, solve)]
        ops += [Op("greedy_pick_resident.1", at(5 + 2 * i), at(6 + 2 * i),
                   DEV, solve) for i in range(3)]
        ops += [Op("fusion.2", at(12), at(13.5), DEV, "jit__finalize_batch")]
        ops += [Op("dynamic_slice.1", at(15 + i), at(15.1 + i), DEV,
                   "jit_dynamic_slice") for i in range(3)]
        ops += [Op("dynamic_slice.1", at(20.5), at(20.6), DEV,
                   "jit_dynamic_slice")]
    return Reduced(ops, spans)


def _ctx(red, window=None, counters=None):
    return dict(trace=red, window=window,
                shapes={"n_pad": 317184, "words": 512},
                device={"memory_peak_bytes": 13695534592},
                peaks=run.peaks_for("TPU v5 lite"), counters=counters)


# What every per-layer metric of the first benchmark reads on these
# traces: the readers added beside them leave them as they were.
EXISTING = {
    "round": {"s1_sample_ms": 3.5000000000000004, "s3_sender_ms": 0.5,
              "s3_sender_roofline": 158.63072820512818,
              "s4_receiver_ms": 0.5, "idle_share.round": 35.71428571428571,
              "peak_hbm_gb.round": 13.695534592, "serve_solve_ms": None,
              "serve_solve_roofline": None, "idle_share.serve": None},
    "serve": {"s1_sample_ms": None, "s3_sender_ms": None,
              "s3_sender_roofline": None, "s4_receiver_ms": None,
              "idle_share.round": None, "peak_hbm_gb.round": 13.695534592,
              "serve_solve_ms": 7.0, "serve_solve_roofline": 11.3307663003663,
              "idle_share.serve": 57.61904761904761},
    "recorded": {"s1_sample_ms": 0.028425000000000002, "s3_sender_ms": None,
                 "s3_sender_roofline": None, "s4_receiver_ms": None,
                 "idle_share.round": 99.56864673034728,
                 "peak_hbm_gb.round": 13.695534592, "serve_solve_ms": None,
                 "serve_solve_roofline": None, "idle_share.serve": None},
}


def _trace(name):
    if name == "recorded":
        return tr.reduce_file(RECORDED, run.HOST_SPANS)
    return {"round": _round_trace, "serve": _serve_trace}[name]()


@pytest.mark.parametrize("trace", sorted(EXISTING))
def test_existing_metrics_read_as_before(trace):
    ctx = _ctx(_trace(trace))
    assert {m: _read(m, ctx) for m in EXISTING[trace]} == EXISTING[trace]


def test_launches_counted_from_operations():
    ctx = _ctx(_serve_trace())
    # per answer: the solve, its epilogue and three fetches
    assert _read("serve_launches_per_batch", ctx) == 5.0
    assert _read("serve_launches_per_batch", _ctx(_round_trace())) is None


def test_launches_match_module_events_on_a_chip_trace():
    """On the recorded v5e trace the count from the operations is the
    count of the device's own ``XLA Modules`` events."""
    import jax
    pd = jax.profiler.ProfileData.from_file(RECORDED)
    modules = sum(len(list(line.events)) for plane in pd.planes
                  if plane.name.startswith("/device:TPU")
                  for line in plane.lines if line.name == tr.MODULE_LINE)
    red = tr.reduce_file(RECORDED, run.HOST_SPANS)
    launches = load_module("layer_metrics", "serve_launches_per_batch")
    assert modules > 0
    assert launches.launches(red.ops, lambda t: True) == modules


def _window(t0, t1, batches):
    w = Window(unit="batches", t0=t0, t1=t1)
    w.items = [Item(t0, t1, 8, 8) for _ in range(batches)]
    return w


def test_span_readers_read_the_window(monkeypatch):
    from repro.runtime import spans
    record = spans.RECORD.__class__(maxlen=16)
    record.extend([("service.prepare", 0.5, 1.5),     # before the window
                   ("service.prepare", 10.0, 10.002),
                   ("service.dispatch", 10.002, 10.003),
                   ("service.answers", 10.010, 10.070),
                   ("service.prepare", 11.0, 11.004),
                   ("service.answers", 11.010, 11.050)])
    monkeypatch.setattr(spans, "RECORD", record)
    ctx = _ctx(_serve_trace(), window=_window(9.0, 12.0, 2))
    assert _read("serve_prepare_ms", ctx) == pytest.approx(3.0)
    assert _read("serve_dispatch_ms", ctx) == pytest.approx(0.5)
    assert _read("serve_answers_ms", ctx) == pytest.approx(50.0)
    # a renamed span is an error, not a zero
    with pytest.raises(KeyError):
        program_record.span_ms_per_batch(ctx, "service.answer")
    # a record that lost the window's start is an error
    record.extend([("service.wait", 12.5, 12.6)] * 16)
    with pytest.raises(RuntimeError):
        _read("serve_prepare_ms", ctx)


def test_span_readers_silent_without_program_spans(monkeypatch):
    monkeypatch.setattr(program_record, "_spans", lambda: None)
    ctx = _ctx(_serve_trace(), window=_window(0.0, 1.0, 2))
    for name in ("serve_prepare_ms", "serve_dispatch_ms",
                 "serve_answers_ms"):
        assert _read(name, ctx) is None


COUNTED = dict(rounds=2, bfs_steps=14, rrr_pairs=700, sender_tiles_swept=16,
               coins_per_bfs_step=1000, sender_picks=8,
               sender_tiles_per_pick=2)


def test_round_counter_readers():
    ctx = _ctx(_round_trace(), counters=COUNTED)
    # S1: 3.5 ms a round (test_existing_metrics_read_as_before), 7 steps
    assert _read("s1_step_ms", ctx) == pytest.approx(0.5)
    assert _read("s1_coins_per_pair", ctx) == pytest.approx(20.0)
    assert _read("s3_sweep_share", ctx) == pytest.approx(50.0)
    for name in ("s1_step_ms", "s1_coins_per_pair", "s3_sweep_share"):
        assert _read(name, _ctx(_round_trace(), counters={})) is None


def _out(bfs_steps, rrr_pairs, swept):
    return SimpleNamespace(seeds=None, bfs_steps=bfs_steps,
                           rrr_pairs=rrr_pairs, sender_tiles_swept=swept)


def test_round_counters_read_off_the_driver_state():
    """Called by the harness's ``run_cell``, the readers sum the window's
    outputs held in its driver state, over the program's own units."""
    from repro.core.greediris import round_units
    cfg = {"sample_chunks": 4, "coin_chunk": 32}
    cell = SimpleNamespace(chips=1)

    def run_cell(state, cell, name):
        return _read(name, _ctx(_round_trace()))

    state = SimpleNamespace(cfg=cfg, n=1000, theta=16384, k=10, d_max=21,
                            model="IC", outputs=[(0, _out(30, 900, 40)),
                                                 (1, _out(36, 1100, 44))])
    u = round_units(n=1000, theta=16384, k=10, max_degree=21, machines=1,
                    model="IC", sample_chunks=4, coin_chunk=32)
    assert u.coins_per_bfs_step == 4096 * 1000 * 21
    assert run_cell(state, cell, "s1_coins_per_pair") == pytest.approx(
        66 * u.coins_per_bfs_step / 2000)
    assert run_cell(state, cell, "s3_sweep_share") == pytest.approx(
        100 * 84 / (2 * 10 * u.sender_tiles_per_pick))
    # S1 3.5 ms a round over 33 steps a round
    assert run_cell(state, cell, "s1_step_ms") == pytest.approx(3.5 / 33)
    # outputs of a program that counts nothing, or no harness: silent
    state.outputs = [(0, SimpleNamespace(seeds=None))]
    assert run_cell(state, cell, "s1_coins_per_pair") is None
    assert _read("s3_sweep_share", _ctx(_round_trace())) is None


def _run(root, name):
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cell = run.Cell(bench, name, root=root)
    return run.run_cell(cell, seed=2 ** 31 + 17, seconds=0.2, trace=True,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("name,reads", [
    ("tiny_ic.round", ("s1_coins_per_pair", "s3_sweep_share")),
    ("tiny_ic.serve", ("serve_prepare_ms", "serve_dispatch_ms",
                       "serve_answers_ms"))])
def test_tiny_traced_run_reads_spans_and_counters(tiny_root, monkeypatch,
                                                  name, reads):
    """A traced tiny run on the CPU: the program's spans and the round's
    counters reach their readers (device metrics need a TPU's trace)."""
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    res = _run(tiny_root, name)
    assert res["correct"]
    for m in reads:
        assert res["metrics"][m]["value"] > 0
    if name.endswith("round"):
        assert res["metrics"]["s3_sweep_share"]["value"] <= 100.0


def test_service_spans_in_a_profile(tmp_path):
    """Two tiny batches through ``InfluenceService.answer`` under the
    profiler: each program span once per batch, in order, inside
    ``answer``, on the trace and in the span record alike."""
    import jax
    from repro.core.service import InfluenceService, Query
    from repro.graphs.csr import from_edge_list
    from repro.runtime.spans import RECORD, SPANS
    from bench import gen
    src, dst = gen.edge_list({"generator": "gnm_undirected", "n": 64,
                              "edges": 192, "structure_seed": 0})
    svc = InfluenceService(from_edge_list(src, dst, 64, seed=1),
                           jax.random.key(2), theta0=256, max_theta=256,
                           slab=128, solver="resident", sampler="packed")
    batch = [Query(k=2), Query(k=3, excluded=(1, 2))]
    svc.answer([svc.admit(q) for q in batch])     # compile outside
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("answer"):
                svc.answer([svc.admit(q) for q in batch])
    finally:
        jax.profiler.stop_trace()
    r = tr.reduce_dir(str(tmp_path), ("answer",) + SPANS)
    answers = [s for s in r.spans if s.name == "answer"]
    assert len(answers) == 2
    for a in answers:
        inside = [s.name for s in r.spans if s.name != "answer"
                  and a.start <= s.start and s.end <= a.end]
        assert inside == list(SPANS)
    assert len(r.spans) == 2 * (1 + len(SPANS))
    recorded = [(n, s, e) for n, s, e in RECORD if s >= t0]
    assert [n for n, _, _ in recorded] == list(SPANS) * 2
    assert all(s <= e for _, s, e in recorded)
