"""The control (the reference in bfloat16, in the program's place)
reads as not correct through each cell's own check, and the reference
in float32 reads as correct; at a size where enough coins sit near
their thresholds for bfloat16 to flip some."""
import json
import os

import pytest

from bench_tiny import TINY_IC, TINY_SERVE

from bench import control, run

MID = dict(TINY_IC, graph=dict(TINY_IC["graph"], n=2048),
           theta_per_chip=2048, k=16,
           service={"theta": 1024, "slab": 512, "solver": "resident",
                    "sampler": "packed"})


def _cell(tmp_path, cfg, traffic_name, traffic):
    os.makedirs(tmp_path / "bench" / "configs", exist_ok=True)
    os.makedirs(tmp_path / "bench" / "traffic", exist_ok=True)
    json.dump(cfg, open(tmp_path / "bench" / "configs" / "mid.json", "w"))
    json.dump(traffic, open(tmp_path / "bench" / "traffic" /
                            f"{traffic_name}.json", "w"))
    bench = {"configs": [{"name": cfg["name"],
                          "file": "bench/configs/mid.json"}],
             "workloads": [{"name": "mid", "config": cfg["name"],
                            "traffic": traffic_name, "chips": 1}],
             "end_to_end": [], "per_layer": []}
    return run.Cell(bench, "mid", root=str(tmp_path))


ROUND = json.load(open(os.path.join(run.BENCH, "traffic", "round.json")))


@pytest.mark.parametrize("model", ["IC", "LT"])
def test_round_control_fails(tmp_path, model):
    cfg = dict(MID, name="mid", model=model, bfs_steps_per_round=None)
    cell = _cell(tmp_path, cfg, "round", ROUND)
    out = control.control(cell, seed=2 ** 31 + 3)
    assert not out["correct"]
    assert out["checks"]["seed_mismatch"]["value"] > 0


def test_round_reference_in_place_passes(tmp_path):
    cfg = dict(MID, name="mid", bfs_steps_per_round=None)
    cell = _cell(tmp_path, cfg, "round", ROUND)
    driver = run.load_module("drivers", "round")
    state = driver.control_state(cell, 2 ** 31 + 3, "f32")
    checks, failed = driver.check(state, None)
    assert failed == 0 and all(c["value"] == 0 for c in checks.values())


def test_serve_control_fails(tmp_path):
    traffic = dict(TINY_SERVE, k_max=16)
    cell = _cell(tmp_path, dict(MID, name="mid"), "serve", traffic)
    out = control.control(cell, seed=2 ** 31 + 3)
    assert not out["correct"]
    assert out["checks"]["answer_mismatch"]["value"] > 0


def test_serve_reference_in_place_passes(tmp_path):
    traffic = dict(TINY_SERVE, k_max=16)
    cell = _cell(tmp_path, dict(MID, name="mid"), "serve", traffic)
    driver = run.load_module("drivers", "serve")
    state = driver.control_state(cell, 2 ** 31 + 3, "f32")

    class W:
        def attempted(self):
            return 0

    checks, failed = driver.check(state, W())
    assert failed == 0 and all(c["value"] == 0 for c in checks.values())
