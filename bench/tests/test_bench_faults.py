"""Whole tiny runs with the timed path broken underneath: each fault a
cell can have must turn ``correct`` false (the look for a chip is
skipped; the program runs on the CPU)."""
import json
import os
import time

import numpy as np

import jax

from bench_tiny import tiny_root  # noqa: F401
from bench import run


def _run(tiny_root, name):
    bench = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    cell = run.Cell(bench, name, root=tiny_root)
    return run.run_cell(cell, seed=2 ** 31 + 41, seconds=0.3, trace=False,
                        t_start=time.perf_counter())


def _patch_round(monkeypatch, wrap, **override):
    from repro.core import greediris
    real = greediris.build_round

    def build(*a, **kw):
        kw.update(override)
        fn, n_pad, theta = real(*a, **kw)
        return wrap(fn), n_pad, theta

    monkeypatch.setattr(greediris, "build_round", build)


def test_round_answer_altered(tiny_root, monkeypatch):
    def wrap(fn):
        def broken(*a):
            out = fn(*a)
            return out._replace(seeds=out.seeds.at[0].add(1))
        return broken
    _patch_round(monkeypatch, wrap)
    res = _run(tiny_root, "tiny_ic.round")
    assert not res["correct"] and res["failed"] >= 1
    assert res["checks"]["seed_mismatch"]["value"] > 0


def test_round_state_unchanged(tiny_root, monkeypatch):
    """Every round returns the answer of one stale key."""
    def wrap(fn):
        def broken(nbr, prob, wt, key):
            return fn(nbr, prob, wt, jax.random.key(123456))
        return broken
    _patch_round(monkeypatch, wrap)
    res = _run(tiny_root, "tiny_lt.round")
    assert not res["correct"]


def test_round_half_the_sets_left_out(tiny_root, monkeypatch):
    _patch_round(monkeypatch, lambda fn: fn, theta=128)
    res = _run(tiny_root, "tiny_ic.round")
    assert not res["correct"]


def _patch_answer(monkeypatch, broken):
    from repro.core.service import InfluenceService
    real = InfluenceService.answer

    def answer(self, tickets):
        return broken(self, real, tickets)
    monkeypatch.setattr(InfluenceService, "answer", answer)


def test_serve_answer_altered(tiny_root, monkeypatch):
    calls = []

    def broken(self, real, tickets):
        out = real(self, tickets)
        calls.append(1)
        if len(calls) > 3:
            out[0] = out[0]._replace(coverage=out[0].coverage + 1)
        return out
    _patch_answer(monkeypatch, broken)
    res = _run(tiny_root, "tiny_ic.serve")
    assert not res["correct"] and res["checks"]["coverage_gap"]["value"] > 0


def test_serve_state_unchanged(tiny_root, monkeypatch):
    """Each batch gets the previous batch's answers back."""
    last = {}

    def broken(self, real, tickets):
        out = real(self, tickets)
        prev = last.get(len(tickets), out)
        last[len(tickets)] = out
        return prev
    _patch_answer(monkeypatch, broken)
    res = _run(tiny_root, "tiny_ic.serve")
    assert not res["correct"]


def test_serve_half_the_batch_left_out(tiny_root, monkeypatch):
    """Only the first half of a batch is solved; the rest get copies."""
    def broken(self, real, tickets):
        h = max(1, len(tickets) // 2)
        out = real(self, tickets[:h])
        rest = [out[i % h] for i in range(len(tickets) - h)]
        self.release(tickets[h:])
        return out + rest
    _patch_answer(monkeypatch, broken)
    res = _run(tiny_root, "tiny_ic.serve")
    assert not res["correct"]
    assert res["checks"]["answer_mismatch"]["value"] > 0
    assert np.isfinite(res["checks"]["bound_gap"]["value"])
