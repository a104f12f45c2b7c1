"""Chip smoke test: the GreediRIS round and the query service on a TPU.

    python chip_smoke.py               # one chip: the main path
    python chip_smoke.py --four-chips  # four chips: the distributed round

Drives the system once through its library entry points, on an
Erdos-Renyi graph of 2^18 vertices (average degree 8, IC diffusion
with p ~ U[0, 0.1], every edge kept):

  1. device  - anything but a TPU is refused; there is no CPU fallback;
  2. round   - the fixed-theta GreediRIS round (``build_round``, the
               call ``im_driver --theta`` makes) with the lazy and the
               resident Pallas senders and the Pallas receiver, each
               bit-identical to the scan reference;
  3. kernels - every ``pallas_call`` of the kernel round is compiled
               (``interpret=False``), the sender and receiver launches
               are there, and the compiled HLO holds ``tpu_custom_call``;
  4. spread  - Monte-Carlo spread of the seeds (packed cascade engine);
  5. service - ``InfluenceService`` batched answers equal the
               sequential ``answer_one`` answers bit for bit.

``--four-chips`` runs only the round on a 4-device ``machines`` mesh
(theta 16384 per chip), gather and pipeline aggregation, each with the
Pallas sender + receiver bit-identical to the scan reference, and
checks that the outputs span all four devices.

Times are the host clock around ``block_until_ready``: not a
benchmark.  Every check raises on failure; the last line of stdout is
one JSON object naming the device, printed only when all passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.analysis.jaxpr_check import launch_sites  # noqa: E402
from repro.core import greediris  # noqa: E402
from repro.core.diffusion import influence  # noqa: E402
from repro.core.service import InfluenceService  # noqa: E402
from repro.graphs import generators  # noqa: E402
from repro.graphs.csr import (padded_adjacency,  # noqa: E402
                              padded_forward_adjacency)
from repro.launch import serve  # noqa: E402
from repro.launch.mesh import make_im_mesh  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# The cell: n = 2^18, avg degree 8 (about 2.1M edges, every one kept),
# k = 100, theta = 16384 per chip sampled in 4 chunks.
N_LOG2 = 18
AVG_DEG = 8
K = 100
THETA_PER_CHIP = 16384
SAMPLE_CHUNKS = 4
SPREAD_SIMS = 256
SERVICE_BATCHES = 2
SERVICE_BATCH = 8
SERVICE_SLAB = 4096

# Round variants: (solver, kernel receiver).  "scan" is the plain jnp
# reference every kernel variant must match bit for bit.
VARIANTS = {
    "lazy": dict(solver="lazy", use_kernel=True),
    "resident": dict(solver="resident", use_kernel=True),
    "scan": dict(solver="scan", use_kernel=False),
}
SENDERS = ("lazy_greedy", "greedy_pick_resident")
RECEIVERS = ("bucket_insert_stream", "bucket_insert_chunk")
OUT_FIELDS = ("seeds", "coverage", "global_coverage", "best_local_coverage")


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def build_graph(n: int, avg_deg: float, seed: int):
    """The cell's graph with every edge kept: the padded reverse and
    forward adjacencies at the true max degrees (no ``pad_to``)."""
    g = generators.erdos_renyi(n, avg_deg, seed=seed)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    kept = int((np.asarray(nbr) >= 0).sum())
    if kept != g.num_edges:
        raise AssertionError(f"adjacency keeps {kept} of {g.num_edges} "
                             f"edges")
    if int((np.asarray(fwd[0]) >= 0).sum()) != g.num_edges:
        raise AssertionError("forward adjacency dropped edges")
    return g, (nbr, prob, wt), fwd


def build(mesh, g, fwd, *, theta: int, k: int, sample_chunks: int,
          aggregate: str, variant: str):
    """``build_round`` as ``im_driver --theta`` calls it, packed
    sampler, one variant of VARIANTS."""
    fn, _, theta_total = greediris.build_round(
        mesh, ("machines",), n=g.num_vertices, theta=theta, k=k,
        max_degree=g.max_in_degree(), model="IC", aggregate=aggregate,
        sampler="packed", fwd=fwd, sample_chunks=sample_chunks,
        **VARIANTS[variant])
    return fn, theta_total


def place(mesh, arrays, key):
    """Replicate the graph and key over the mesh."""
    rep = NamedSharding(mesh, P())
    return tuple(jax.device_put(a, rep) for a in (*arrays, key))


def run_round(fn, args) -> dict:
    """Compile and run one round; outputs as numpy, host-clock times,
    the compiled HLO text and the output arrays themselves."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return dict(out={f: np.asarray(getattr(out, f)) for f in OUT_FIELDS},
                arrays=out, compile_s=t1 - t0, run_s=t2 - t1,
                hlo=compiled.as_text())


def check_identical(name: str, got: dict, ref: dict):
    for f in OUT_FIELDS:
        if not np.array_equal(got[f], ref[f]):
            raise AssertionError(f"{name}: {f} differs from the scan "
                                 f"reference: {got[f]} vs {ref[f]}")


def kernel_launches(fn, args) -> list:
    """(name, interpret) of every pallas_call in the traced round."""
    return [(s.name, s.interpret)
            for s in launch_sites(jax.make_jaxpr(fn)(*args))]


def check_launches(launches: list):
    names = {n for n, _ in launches}
    interpreted = [n for n, i in launches if i]
    if interpreted:
        raise AssertionError(f"interpreted kernels: {interpreted}")
    if not names & set(SENDERS):
        raise AssertionError(f"no sender kernel among {sorted(names)}")
    if not names & set(RECEIVERS):
        raise AssertionError(f"no receiver kernel among {sorted(names)}")


def check_kernel_round(name: str, fn, args, hlo: str) -> list:
    """Phase 3: the kernel round runs real Mosaic kernels."""
    if "tpu_custom_call" not in hlo:
        raise AssertionError(f"{name}: no tpu_custom_call in the "
                             f"compiled round")
    launches = kernel_launches(fn, args)
    check_launches(launches)
    return launches


def spread(g, seeds, *, sims: int, seed: int) -> dict:
    """Monte-Carlo spread of the seeds, cold (compile + run) then warm;
    both calls draw the same coins, so they must agree exactly."""
    key = jax.random.fold_in(jax.random.key(seed), 99)
    t0 = time.perf_counter()
    cold = float(influence(g, seeds, key, num_sims=sims, engine="packed"))
    t1 = time.perf_counter()
    warm = float(influence(g, seeds, key, num_sims=sims, engine="packed"))
    t2 = time.perf_counter()
    k_real = int((np.asarray(seeds) >= 0).sum())
    if cold != warm:
        raise AssertionError(f"spread not reproducible: {cold} vs {warm}")
    if not (np.isfinite(cold) and k_real <= cold <= g.num_vertices):
        raise AssertionError(f"spread {cold} outside [{k_real}, "
                             f"{g.num_vertices}]")
    return dict(spread=cold, cold_s=t1 - t0, warm_s=t2 - t1)


def run_service(g, *, theta0: int, slab: int, batches: int, batch: int,
                k_max: int, seed: int) -> dict:
    """The query service as ``launch/serve.py --check`` drives it:
    resident pool of ``theta0`` samples per OPIM half, ``batches``
    vmapped batches of mixed-k, partly seed-constrained queries, each
    answer bit-identical to the sequential ``answer_one``."""
    service = InfluenceService(
        g, jax.random.key(seed), theta0=theta0, max_theta=theta0,
        slab=slab, solver="resident", sampler="packed")
    trace = serve.make_trace(g.num_vertices, batches * batch, seed + 1,
                             k_max=k_max)
    t0 = time.perf_counter()
    service.refresh()
    jax.block_until_ready((service.pool.r1, service.pool.r2))
    t1 = time.perf_counter()
    answers, pools, cold_s = serve.replay(service, trace, batch=batch)
    again, _, warm_s = serve.replay(service, trace, batch=batch)
    if not all(serve.answers_equal(a, b) for a, b in zip(answers, again)):
        raise AssertionError("a second replay answered differently")
    t2 = time.perf_counter()
    bad = serve.check_bit_identity(service, pools, trace, answers)
    t3 = time.perf_counter()
    if bad:
        raise AssertionError(f"{bad}/{len(trace)} batched answers differ "
                             f"from the sequential reference")
    return dict(theta=service.pool.theta, answers=answers, trace=trace,
                pool_s=t1 - t0, cold_s=cold_s, warm_s=warm_s,
                check_s=t3 - t2)


def memory_line() -> str:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", "n/a"))
    return f"peak_bytes_in_use per device: {peaks}"


def one_chip(*, n_log2: int = N_LOG2, theta: int = THETA_PER_CHIP,
             k: int = K, sample_chunks: int = SAMPLE_CHUNKS,
             sims: int = SPREAD_SIMS, slab: int = SERVICE_SLAB) -> dict:
    """Phases 2-5 on a one-device mesh; returns the round outputs."""
    mesh = make_im_mesh(1)
    t0 = time.perf_counter()
    g, arrays, fwd = build_graph(2 ** n_log2, AVG_DEG, seed=0)
    log(f"graph n={g.num_vertices} edges={g.num_edges} "
        f"max_in_degree={g.max_in_degree()} (every edge kept; host "
        f"set-up {time.perf_counter() - t0:.2f}s)")
    args = place(mesh, arrays, jax.random.key(0))

    results = {}
    for variant in ("scan", "lazy", "resident"):
        fn, theta_total = build(mesh, g, fwd, theta=theta, k=k,
                                sample_chunks=sample_chunks,
                                aggregate="gather", variant=variant)
        r = run_round(fn, args)
        results[variant] = r
        log(f"round {variant}: theta={theta_total} k={k} "
            f"coverage={int(r['out']['coverage'])} "
            f"(global {int(r['out']['global_coverage'])}, best-local "
            f"{int(r['out']['best_local_coverage'])}); compile "
            f"{r['compile_s']:.2f}s run {r['run_s']:.3f}s "
            f"(host clock, not a benchmark)")
        if variant != "scan":
            check_identical(variant, r["out"], results["scan"]["out"])
            launches = check_kernel_round(variant, fn, args, r["hlo"])
            log(f"round {variant}: bit-identical to scan in "
                f"{', '.join(OUT_FIELDS)}; kernels {launches} all "
                f"interpret=False; HLO has tpu_custom_call")
    seeds = results["lazy"]["out"]["seeds"]

    cov = int(results["lazy"]["out"]["coverage"])
    s = spread(g, seeds, sims=sims, seed=0)
    log(f"spread of the {int((seeds >= 0).sum())} seeds: {s['spread']} "
        f"over {sims} simulations (sketch estimate "
        f"{cov * g.num_vertices / theta_total}); compile+run "
        f"{s['cold_s']:.2f}s, run {s['warm_s']:.3f}s")

    sv = run_service(g, theta0=theta, slab=slab, batches=SERVICE_BATCHES,
                     batch=SERVICE_BATCH, k_max=k, seed=0)
    ks = [q.k for q in sv["trace"]]
    constrained = sum(bool(q.excluded) for q in sv["trace"])
    log(f"service: pool theta={sv['theta']} per half, "
        f"{len(sv['answers'])} queries in {SERVICE_BATCHES} batches of "
        f"{SERVICE_BATCH} (k {ks}, {constrained} seed-constrained); "
        f"pool fill {sv['pool_s']:.2f}s, replay compile+run "
        f"{sv['cold_s']:.2f}s, replay run {sv['warm_s']:.3f}s, "
        f"sequential check {sv['check_s']:.2f}s")
    log("service: batched answers bit-identical to sequential answer_one")
    log(memory_line())
    return {v: r["out"] for v, r in results.items()}


def four_chips(*, n_log2: int = N_LOG2, theta_per_chip: int = THETA_PER_CHIP,
               k: int = K, sample_chunks: int = SAMPLE_CHUNKS) -> dict:
    """The distributed round alone on a 4-device mesh; returns the
    outputs by (aggregate, variant)."""
    mesh = make_im_mesh(4)
    g, arrays, fwd = build_graph(2 ** n_log2, AVG_DEG, seed=0)
    log(f"graph n={g.num_vertices} edges={g.num_edges} "
        f"max_in_degree={g.max_in_degree()} (every edge kept)")
    args = place(mesh, arrays, jax.random.key(0))
    mesh_devices = set(mesh.devices.flat)
    results = {}
    for aggregate in ("gather", "pipeline"):
        for variant in ("scan", "lazy"):
            fn, theta = build(mesh, g, fwd, theta=4 * theta_per_chip,
                              k=k, sample_chunks=sample_chunks,
                              aggregate=aggregate, variant=variant)
            r = run_round(fn, args)
            results[aggregate, variant] = r["out"]
            spans = {d for a in r["arrays"] for d in a.sharding.device_set}
            if spans != mesh_devices:
                raise AssertionError(f"{aggregate}/{variant} outputs on "
                                     f"{spans}, not the 4-device mesh")
            log(f"round {aggregate}/{variant}: m=4 theta={theta} "
                f"coverage={int(r['out']['coverage'])} (global "
                f"{int(r['out']['global_coverage'])}, best-local "
                f"{int(r['out']['best_local_coverage'])}); outputs span "
                f"{len(spans)} devices; compile {r['compile_s']:.2f}s run "
                f"{r['run_s']:.3f}s (host clock, not a benchmark)")
            if variant != "scan":
                check_identical(f"{aggregate}/{variant}", r["out"],
                                results[aggregate, "scan"])
                launches = check_kernel_round(f"{aggregate}/{variant}",
                                              fn, args, r["hlo"])
                log(f"round {aggregate}/{variant}: bit-identical to scan; "
                    f"kernels {launches} all interpret=False")
    # Gather and pipeline are not bit-identical to each other: under
    # "pipeline" device j's receiver streams the candidates in ring
    # order from j, and the round keeps the best of those m orders.
    log("gather and pipeline: kernel round bit-identical to the scan "
        "reference for each; coverage gather="
        f"{int(results['gather', 'scan']['coverage'])} pipeline="
        f"{int(results['pipeline', 'scan']['coverage'])}")
    log(memory_line())
    return results


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed round on 4 chips")
    args = ap.parse_args(argv)
    info = device_info()
    if info["platform"] != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {info['count']} "
              f"{info['platform']} device(s) ({info['kind']}); this "
              f"smoke test runs only on a TPU", file=sys.stderr)
        return 1
    log(f"device {info['kind']} x{info['count']} ({info['platform']})")
    if args.four_chips:
        if info["count"] < 4:
            print(f"[chip_smoke] --four-chips needs 4 devices, found "
                  f"{info['count']}", file=sys.stderr)
            return 1
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
