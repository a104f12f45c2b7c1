"""``serve_stream_share``: the batched solves' pool reads over one pool
stream per query and pick, read from the program's solve record inside
the window, and silent for a program that keeps no such record."""
import collections
import time

import numpy as np
import pytest

from bench_tiny import TINY_SERVE, tiny_root  # noqa: F401
from bench import run
from bench.find import load_module
from bench.window import Item, Window


def _read(ctx):
    return load_module("layer_metrics", "serve_stream_share").read(ctx)


def _window(t0, t1, batches):
    w = Window(unit="batches", t0=t0, t1=t1)
    w.items = [Item(t0, t1, 8, 8) for _ in range(batches)]
    return w


def test_silent_without_the_program_record(monkeypatch):
    from repro.core import service
    monkeypatch.delattr(service, "SOLVES")
    assert _read({"window": _window(0.0, 1.0, 2)}) is None


def test_reads_the_window_only(monkeypatch):
    from repro.core import service
    record = collections.deque(maxlen=8)
    S = service.Solve
    record.extend([S(0.5, 8, 8, 10, np.int32(640)),    # before: 100%
                   S(10.0, 8, 8, 10, np.int32(80)),     # 12.5%
                   S(11.0, 4, 2, 10, np.int32(20)),     # 25%
                   S(12.5, 1, 1, 10, np.int32(10))])    # after
    monkeypatch.setattr(service, "SOLVES", record)
    ctx = {"window": _window(9.0, 12.0, 2)}
    assert _read(ctx) == pytest.approx(100.0 * 100 / (640 + 80))
    assert _read({"window": _window(20.0, 21.0, 1)}) is None
    # a record that lost the window's start is an error, not a share
    record.extend([S(12.6, 1, 1, 10, np.int32(10))] * 8)
    with pytest.raises(RuntimeError):
        _read(ctx)


def test_tiny_serve_run_reads_one_stream_per_batch(tiny_root, monkeypatch):
    """A traced tiny serve run on the CPU: every batch of B queries
    shares one pool stream per pick, so the share reads 100 / B."""
    import json
    import os
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cell = run.Cell(bench, "tiny_ic.serve", root=tiny_root)
    res = run.run_cell(cell, seed=2 ** 31 + 23, seconds=0.2, trace=True,
                       t_start=time.perf_counter())
    assert res["correct"]
    assert res["metrics"]["serve_stream_share"]["value"] == pytest.approx(
        100.0 / TINY_SERVE["batch"])
