"""Benchmark of the GreediRIS round and the query service on a TPU.

    python bench/run.py --workload er18_ic.round --seed 7 --seconds 25 --trace 0

Runs one cell of ``BENCHMARK.json`` in this process: refuses anything
but a TPU with the chips the cell asks for, builds the cell's inputs
from ``--seed``, compiles and warms the cell's own shapes (set-up;
the harness's own work there, ``harness_s``, is left out of it),
measures for ``--seconds``, checks what the timed path produced against
the plain reference (``bench/reference``), and prints one JSON object
as the last line of stdout.  With ``--trace 0`` its metrics are the
cell's end-to-end metrics; with ``--trace 1`` the window is profiled
and the metrics are the cell's per-layer metrics, read from the trace.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic mix ``bench/traffic/<traffic>.json``,
the mix's driver ``bench/drivers/<driver>.py``, each end-to-end metric
``bench/end_to_end/<metric>.py`` and each per-layer metric
``bench/layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from bench.find import load_json, load_module  # noqa: E402

HOST_SPANS = ("round", "admit", "answer", "epilogue")


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            root, "bench", "traffic", f"{self.entry['traffic']}.json"))
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]


class ProgramMemory:
    """Records the device memory of every program compiled or loaded
    while it is entered: its arguments, outputs and temporaries, less
    what outputs alias, as the compiler assigned them.  The allocator's
    ``peak_bytes_in_use`` on a TPU sees only the buffers handed to the
    host, not a program's temporaries."""

    def __init__(self):
        from jax._src import compiler
        self.compiler = compiler
        self.real = compiler.compile_or_get_cached
        self.largest = (0, {})

    def _compile(self, *args, **kw):
        exe = self.real(*args, **kw)
        m = exe.get_compiled_memory_stats()
        parts = {"arguments": m.argument_size_in_bytes,
                 "outputs": m.output_size_in_bytes,
                 "temporaries": m.temp_size_in_bytes,
                 "aliased": m.alias_size_in_bytes}
        total = (parts["arguments"] + parts["outputs"]
                 + parts["temporaries"] - parts["aliased"])
        if total > self.largest[0]:
            self.largest = (total, parts)
        return exe

    def __enter__(self):
        self.compiler.compile_or_get_cached = self._compile
        return self

    def __exit__(self, *exc):
        self.compiler.compile_or_get_cached = self.real


def device_block(jax, programs: ProgramMemory) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the larger
    of the allocator's peak and the largest program's footprint, on the
    fullest chip."""
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    largest, parts = programs.largest
    log(f"device memory: allocator peak {max(peaks)} B; largest program "
        f"{largest} B {parts}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(max(max(peaks), largest))}


class CompileCounter:
    """Counts tracing, lowering and backend compilation events while
    it is entered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.monitoring = jax.monitoring
        self.count = 0

    def _hear(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        self.monitoring.register_event_duration_secs_listener(self._hear)
        return self

    def __exit__(self, *exc):
        self.monitoring.unregister_event_duration_listener(self._hear)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, keep_trace: str | None = None) -> dict:
    """Set up, measure and check one cell; return the result object.
    ``keep_trace`` copies the raw trace into that directory."""
    import jax

    driver = load_module("drivers", cell.traffic["driver"])
    with ProgramMemory() as programs:
        state = driver.setup(cell, seed=seed)
        setup_s = time.perf_counter() - t_start - state.harness_s
        log(f"set-up {setup_s:.3f}s: {state.describe()}")

        trace_dir = (tempfile.mkdtemp(prefix="bench_trace_") if trace
                     else None)
        with CompileCounter(jax) as counter:
            if trace:
                jax.profiler.start_trace(trace_dir)
            window = driver.window(state, seconds)
            if trace:
                jax.profiler.stop_trace()
    device = device_block(jax, programs)
    log(f"window {window.elapsed():.3f}s, {len(window.items)} "
        f"{window.unit}; compiles in window: {counter.count}; "
        f"peak bytes {device['memory_peak_bytes']}")
    if counter.count:
        log("WARNING: the window compiled; its timing includes that")

    metrics, breakdown = {}, None
    if trace:
        from bench import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, HOST_SPANS)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = dict(trace=red, window=window, shapes=state.shapes(),
                   device=device, peaks=peaks_for(device["kind"]))
        for m in cell.per_layer:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = red.breakdown()
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = load_module("end_to_end", m["name"]).read(window)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.release(state)
    checks, failed = driver.check(state, window)
    correct = (bool(checks) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": window.attempted(),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw profiler trace there")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = Cell(bench, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"no TPU with {cell.chips} chip(s): JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        return 2
    peaks_for(devs[0].device_kind)
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"cell {cell.name} seed {args.seed} on {len(devs)} x "
        f"{devs[0].device_kind}; compile cache {cache}")

    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_PROCESS,
                      keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
