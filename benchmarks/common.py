"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def timeit(fn, *args, warmup: int = 1, iters: int = 3,
           reduce: str = "median"):
    """Wall-clock seconds of fn(*args) after warmup.

    reduce="median" for reporting; reduce="min" for the CI regression
    gate — the minimum is the statistic least sensitive to scheduler /
    noisy-neighbour contention on shared runners (any single quiet
    iteration recovers the true cost)."""
    if reduce not in ("min", "median"):
        raise ValueError(f"reduce must be 'min' or 'median', "
                         f"got {reduce!r}")
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[0] if reduce == "min" else times[len(times) // 2]


def run_devices(code: str, num_devices: int, timeout: int = 560) -> dict:
    """Run snippet with N fake host devices; snippet must print one
    JSON object on its last line.  The child is held to the CPU, so
    it never tries to take a chip that its parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={num_devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
