"""The reduction from a profiler trace to busy time, idle gaps and
per-layer device time."""
import glob
import os

import pytest

import bench_tiny  # noqa: F401
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Reduced, Span

MS = 1_000_000


def _synthetic():
    ops = [Op("fusion.1", 0 * MS, 4 * MS, "/device:TPU:0", "jit_round"),
           Op("lazy_greedy", 4 * MS, 5 * MS, "/device:TPU:0", "jit_round"),
           Op("fusion.1", 9 * MS, 12 * MS, "/device:TPU:0", "jit_round"),
           Op("bucket_insert_stream", 12 * MS, 13 * MS, "/device:TPU:0",
              "jit_round"),
           Op("fusion.9", 30 * MS, 40 * MS, "/device:TPU:0", "jit_other")]
    spans = [Span("round", 0, 6 * MS), Span("epilogue", 6 * MS, 8 * MS),
             Span("round", 8 * MS, 14 * MS)]
    return Reduced(ops, spans)


def test_busy_and_window():
    r = _synthetic()
    assert r.window_s == pytest.approx(14e-3)
    # busy: [0,5) and [9,13), the op after the window does not count
    assert r.busy_s == pytest.approx(9e-3)
    assert r.count("round") == 2


def test_gaps_named_by_overlapping_span():
    r = _synthetic()
    assert r.gaps() == [(5 * MS, 9 * MS), (13 * MS, 14 * MS)]
    assert r.gap_owner((5 * MS, 9 * MS)) == "epilogue"
    assert r.gap_owner((13 * MS, 14 * MS)) == "round"
    bd = r.breakdown()
    assert bd["idle_gaps"][0] == ["epilogue", pytest.approx(4e-3)]
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(7e-3)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_op_seconds_by_predicate():
    r = _synthetic()
    assert r.op_seconds(lambda o: "lazy" in o.name) == pytest.approx(1e-3)
    assert r.op_seconds(lambda o: o.module == "jit_other") == 0.0


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        Reduced([], [])


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_tpu_trace(path):
    """A trace recorded on one v5e: three ``round`` spans around a
    jitted matmul, ``epilogue`` spans of 5 ms sleep between them."""
    r = tr.reduce_file(path, ("round", "epilogue"))
    assert r.devices == ["/device:TPU:0"]
    assert r.count("round") == 3 and r.count("epilogue") == 3
    assert 0 < r.busy_s < r.window_s
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0][0] == "epilogue" and gaps[0][1] >= 0.004


def test_nested_ops_count_once():
    ops = [Op("while.1", 0, 10 * MS, "/device:TPU:0"),
           Op("fusion.2", 1 * MS, 4 * MS, "/device:TPU:0"),
           Op("fusion.3", 5 * MS, 9 * MS, "/device:TPU:0")]
    r = Reduced(ops, [Span("round", 0, 10 * MS)])
    assert r.op_seconds(lambda o: True) == pytest.approx(10e-3)
    assert [o.name for o in r.leaves()] == ["fusion.2", "fusion.3"]
    assert tr.short_name("%fusion.150 = u32[64,128]{1,0:T(8,128)} "
                         "fusion(%a), kind=kCustom") == "fusion.150 u32[64,128]"
