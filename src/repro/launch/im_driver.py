"""Distributed influence-maximization driver (the paper's end-to-end
application): IMM/OPIM martingale loop with GreediRIS seed selection
on a device mesh.

  PYTHONPATH=src python -m repro.launch.im_driver --n 2000 --avg-deg 8 \
      --k 32 --model IC --selector greediris --machines 4

On CPU the machine count is capped by host devices; run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 for multi-machine
behaviour (the benchmarks do this via subprocesses).
"""
from __future__ import annotations

import argparse
import sys
import time
import warnings

import jax
import numpy as np

from repro.core import greediris, imm, opim, theory
from repro.core.diffusion import influence
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency, padded_forward_adjacency
from repro.launch.mesh import make_host_mesh
from repro.runtime import faults
from repro.runtime.compile_cache import enable_compile_cache


def _coin_chunk_arg(text: str) -> int:
    """--coin-chunk validator: fail at the CLI boundary with an
    actionable message instead of a deep ValueError out of
    ``rrr._coin_chunks`` mid-trace."""
    try:
        v = int(text)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer slot count, got {text!r} (the IC "
            "coin-draw width, e.g. 32)") from None
    if v < 1:
        raise argparse.ArgumentTypeError(
            f"must be >= 1, got {v} — coin-chunk is the number of "
            "adjacency slots each coin draw covers (it is part of the "
            "PRNG stream: pick one value, e.g. 32, and keep it)")
    return v


def _chunk_size_arg(text: str):
    """--chunk-size validator: 'auto', 0 (default policy), or a
    positive candidate count."""
    if text == "auto":
        return "auto"
    try:
        v = int(text)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer candidate count, got "
            f"{text!r} (e.g. --chunk-size auto, --chunk-size 256, or "
            "0 for the default policy)") from None
    if v < 0:
        raise argparse.ArgumentTypeError(
            f"must be >= 0, got {v} — a positive candidate count, 0 "
            "for the default policy, or 'auto' for the VMEM-budget "
            "solve")
    return v or None


def _block_v_arg(text: str):
    """--block-v validator: 'auto' (tuned table / analytic policy) or
    a positive row-tile size."""
    if text == "auto":
        return None
    try:
        v = int(text)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer row-tile size, got "
            f"{text!r} (e.g. --block-v 128)") from None
    if v < 1:
        raise argparse.ArgumentTypeError(
            f"must be >= 1, got {v} — the kernel row-tile size is "
            "rounded up to a multiple of 8 sublanes; 'auto' consults "
            "the tuned table (benchmarks/tuned/) before the analytic "
            "solve")
    return v


def make_graph(kind: str, n: int, avg_deg: float, seed: int):
    if kind == "er":
        return generators.erdos_renyi(n, avg_deg, seed)
    if kind == "ba":
        return generators.preferential_attachment(n, int(avg_deg), seed)
    return generators.rmat(int(np.ceil(np.log2(n))), int(n * avg_deg),
                           seed=seed)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="er", choices=("er", "ba", "rmat"))
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--eps", type=float, default=0.13)
    ap.add_argument("--delta", type=float, default=0.077)
    ap.add_argument("--model", default="IC", choices=("IC", "LT"))
    ap.add_argument("--selector", default="greediris",
                    choices=("greedy", "ripples", "randgreedi",
                             "greediris", "greediris-trunc"))
    ap.add_argument("--alpha", type=float, default=0.125)
    ap.add_argument("--aggregate", default="gather",
                    choices=("gather", "pipeline"))
    ap.add_argument("--machines", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--max-theta", type=int, default=1 << 14)
    ap.add_argument("--theta", type=int, default=0,
                    help="fixed theta (skip martingale loop)")
    ap.add_argument("--use-opim", action="store_true")
    ap.add_argument("--solver", default=None,
                    choices=("scan", "fused", "resident", "lazy"),
                    help="sender (S3) greedy max-k-cover path: 'scan' "
                         "(full sweep + argmax per pick), 'fused' (one "
                         "fused gain+argmax kernel launch per pick), "
                         "'resident' (all k picks in ONE pallas_call, "
                         "state VMEM-resident), or 'lazy' (resident "
                         "plus per-tile stale upper bounds — each pick "
                         "only re-sweeps tiles that can still beat the "
                         "running best); all four bit-identical")
    ap.add_argument("--sampler", default="dense",
                    choices=("dense", "packed", "kernel"),
                    help="S1 RRR sampling path: 'dense' (bool "
                         "[batch, n] BFS state, scatter expansion), "
                         "'packed' (word-packed uint32 [n, batch/32] "
                         "state — 8x fewer state bytes — with a "
                         "gather expansion over the forward "
                         "adjacency), or 'kernel' (packed plus ONE "
                         "fused Pallas launch per BFS step); all "
                         "three bit-identical for the same seed")
    ap.add_argument("--gather", default="auto",
                    choices=("resident", "streamed", "auto"),
                    help="kernel-sampler coin-gather layout: "
                         "'resident' keeps the per-step packed "
                         "coin-plane VMEM-resident and gathers BOTH "
                         "fwd_nbr and rev_slot inside the kernel (no "
                         "XLA-side [n, d_out, W] gmask, no HBM "
                         "round-trip), 'streamed' streams pre-gathered "
                         "gmask tiles (the fallback when the plane "
                         "exceeds VMEM), 'auto' solves from the VMEM "
                         "budget; bit-identical either way (ignored "
                         "by --sampler dense/packed)")
    ap.add_argument("--block-v", type=_block_v_arg, default=None,
                    help="sampler-kernel row-tile size, or 'auto' "
                         "(default: tuned table from 'python -m "
                         "benchmarks.autotune', then the analytic "
                         "VMEM solve); never affects results")
    ap.add_argument("--coin-chunk", type=_coin_chunk_arg, default=32,
                    help="IC coin-draw slot width inside the sampler "
                         "BFS (bounds the bool coin intermediate to "
                         "~batch*n*chunk; the packed samplers also "
                         "hold a [n, d_max, batch/32] packed slot "
                         "mask this knob does not bound; part of the "
                         "PRNG stream, i.e. acts like a seed)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="DEPRECATED: maps to --solver fused and "
                         "additionally routes the receiver through the "
                         "fused/pipelined insertion Pallas kernels")
    ap.add_argument("--chunk-size", type=_chunk_size_arg, default="0",
                    help="receiver insertion chunk: a candidate count "
                         "(>= the stream length forces one whole-stream "
                         "chunk), 'auto' = solve from the VMEM budget, "
                         "or 0 = default ('auto' with --use-kernel, "
                         "whole stream otherwise)")
    ap.add_argument("--eval-sims", type=int, default=32)
    ap.add_argument("--eval-engine", default="packed",
                    choices=("map", "packed", "kernel"),
                    help="cascade engine for the final spread "
                         "evaluation: 'map' (per-simulation lax.map "
                         "reference), 'packed' (word-packed uint32 "
                         "[n, sims/32] state — 8x fewer state bytes), "
                         "or 'kernel' (packed plus ONE fused Pallas "
                         "launch per diffusion step); all three "
                         "bit-identical for the same seed")
    ap.add_argument("--eval-spread", action="store_true",
                    help="after selection, evaluate the returned seed "
                         "set on ALL cascade engines and assert the "
                         "measured spreads are identical (the "
                         "spread-gate cross-check, inline)")
    ap.add_argument("--serve", action="store_true",
                    help="instead of one offline selection, run the "
                         "online serving replay (resident sketch pool "
                         "+ batched queries; see repro.launch.serve) "
                         "on the same graph/model/solver flags")
    ap.add_argument("--faults", action="append", default=[],
                    type=faults.cli_fault_arg,
                    metavar="SITE:KIND[:AT[:ARG]]",
                    help="run the fault-injected resilient round "
                         "(single-controller RandGreedi with a "
                         "survivors-mask merge) under these fault "
                         "specs; at site local.greedy the occurrence "
                         "index is the machine id (e.g. "
                         "'local.greedy:drop:1' loses machine 1, "
                         "'local.greedy:delay:2:0.1' makes machine 2 "
                         "a straggler). Repeatable.")
    ap.add_argument("--fault-report", default=None, metavar="PATH",
                    help="write the JSON fault report (fired events + "
                         "checks) of the --faults round to PATH")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.fault_report and not args.faults:
        ap.error("--fault-report needs --faults (the resilient round "
                 "is what produces the report)")
    if args.serve:
        from repro.launch import serve
        return serve.main([
            "--graph", args.graph, "--n", str(args.n),
            "--avg-deg", str(args.avg_deg), "--model", args.model,
            "--solver", args.solver or "resident",
            "--sampler", args.sampler, "--k-max", str(args.k),
            "--max-theta", str(args.max_theta),
            "--seed", str(args.seed), "--check"])
    chunk_size = args.chunk_size   # validated by _chunk_size_arg
    if args.use_kernel:
        warnings.warn(
            "--use-kernel is deprecated: it maps to --solver fused "
            "(sender) and keeps the kernelized receiver; pass --solver "
            "{scan,fused,resident} explicitly",
            DeprecationWarning)
    solver = args.solver or ("fused" if args.use_kernel else "scan")

    g = make_graph(args.graph, args.n, args.avg_deg, args.seed)
    if args.faults:
        return _main_faulted(args, g, solver)
    n = g.num_vertices
    key = jax.random.key(args.seed)
    print(f"[im] graph n={n} m={g.num_edges} model={args.model} "
          f"selector={args.selector}")

    t0 = time.time()
    if args.selector in ("greediris", "greediris-trunc") and args.theta:
        # fixed-theta distributed round on the device mesh
        mesh = make_host_mesh()
        m = mesh.shape["machines"]
        nbr, prob, wt = padded_adjacency(g)
        fwd = (padded_forward_adjacency(g)
               if args.sampler != "dense" else None)
        alpha = args.alpha if args.selector == "greediris-trunc" else 1.0
        fn, _, theta = greediris.build_round(
            mesh, ("machines",), n=n, theta=args.theta, k=args.k,
            max_degree=g.max_in_degree(), model=args.model,
            delta=args.delta, alpha_trunc=alpha, aggregate=args.aggregate,
            use_kernel=args.use_kernel, solver=solver,
            chunk_size=chunk_size, sampler=args.sampler, fwd=fwd,
            coin_chunk=args.coin_chunk, gather=args.gather,
            block_v=args.block_v)
        out = jax.jit(fn)(nbr, prob, wt, key)
        seeds = np.asarray(out.seeds)
        print(f"[im] m={m} theta={theta} coverage={int(out.coverage)} "
              f"(global {int(out.global_coverage)}, best-local "
              f"{int(out.best_local_coverage)})")
    else:
        m = args.machines or len(jax.devices())
        sel = {
            "greedy": imm.make_greedy_selector(solver),
            "ripples": imm.make_ripples_selector(m),
            "randgreedi": imm.make_randgreedi_selector(
                m, "greedy", solver=solver),
            "greediris": imm.make_randgreedi_selector(
                m, "streaming", args.delta,
                use_kernel=args.use_kernel, solver=solver),
            "greediris-trunc": imm.make_randgreedi_selector(
                m, "streaming", args.delta, args.alpha,
                use_kernel=args.use_kernel, solver=solver),
        }[args.selector]
        if args.use_opim:
            res = opim.opim(g, args.k, args.eps, key, model=args.model,
                            selector=sel, max_theta=args.max_theta,
                            sampler=args.sampler,
                            coin_chunk=args.coin_chunk,
                            gather=args.gather, block_v=args.block_v)
            seeds = res.seeds
            print(f"[im] OPIM rounds={res.rounds} theta={res.theta} "
                  f"guarantee={res.guarantee:.3f} "
                  f"sigma_l={res.sigma_lower:.1f}")
        else:
            res = imm.imm(g, args.k, args.eps, key, model=args.model,
                          selector=sel, max_theta=args.max_theta,
                          sampler=args.sampler,
                          coin_chunk=args.coin_chunk,
                          gather=args.gather, block_v=args.block_v)
            seeds = res.seeds
            print(f"[im] IMM rounds={res.rounds} theta={res.theta} "
                  f"coverage_frac={res.coverage_fraction:.4f}")
    elapsed = time.time() - t0

    # influence() drops -1 pads itself; keep the compact array only
    # for the reported k.
    seeds = np.asarray(seeds)
    k_real = int((seeds >= 0).sum())
    eval_key = jax.random.fold_in(key, 99)
    spread = float(influence(g, seeds, eval_key, model=args.model,
                             num_sims=args.eval_sims,
                             engine=args.eval_engine))
    if args.eval_spread:
        per_engine = {
            eng: float(influence(g, seeds, eval_key, model=args.model,
                                 num_sims=args.eval_sims, engine=eng))
            for eng in ("map", "packed", "kernel")}
        assert len(set(per_engine.values())) == 1, per_engine
        print("[im] spread cross-check: " + "  ".join(
            f"{e}={v:.2f}" for e, v in per_engine.items()) +
            "  (bit-identical)")
    ratio = theory.greediris_ratio(args.delta, args.eps,
                                   args.alpha if "trunc" in args.selector
                                   else 1.0)
    print(f"[im] k={k_real} expected influence = {spread:.1f} "
          f"({100 * spread / n:.2f}% of graph) in {elapsed:.2f}s; "
          f"worst-case ratio {ratio:.3f}")
    return 0


def _main_faulted(args, g, solver: str) -> int:
    """The --faults path: one fixed-theta single-controller RandGreedi
    round driven through :func:`repro.runtime.faults.resilient_randgreedi`
    — injected machine failures become a survivors-mask merge
    (bit-identical to an m'-machine round from scratch, Thm 3.1),
    injected stragglers shrink the §3.3.2 truncation knob through the
    StragglerMonitor."""
    from repro.core import rrr
    from repro.runtime.fault_tolerance import StragglerMonitor

    n = g.num_vertices
    m = args.machines or len(jax.devices())
    theta = args.theta or 1024
    key = jax.random.key(args.seed)
    nbr, prob, wt = padded_adjacency(g)
    fwd = (padded_forward_adjacency(g)
           if args.sampler != "dense" else None)
    rows = rrr.sample_incidence(
        nbr, prob, wt, jax.random.fold_in(key, 1), theta=theta, n=n,
        model=args.model, sampler=args.sampler, fwd=fwd,
        coin_chunk=args.coin_chunk)
    plan = faults.FaultPlan(args.faults)
    monitor = StragglerMonitor()
    alpha0 = args.alpha if "trunc" in args.selector else 1.0
    print(f"[im] resilient round: n={n} theta={theta} m={m} "
          f"k={args.k} faults={len(plan.specs)}")
    report = faults.FaultReport()
    t0 = time.time()
    try:
        res, survivors, alpha_used = faults.resilient_randgreedi(
            rows, jax.random.fold_in(key, 2), m=m, k=args.k,
            plan=plan, monitor=monitor, delta=args.delta,
            alpha_trunc=alpha0, solver=solver)
    except faults.PartitionsLostError as e:
        print(f"[im] FATAL: {e}", file=sys.stderr)
        report.add_events(plan)
        report.check("round_survived", False, error=str(e))
        if args.fault_report:
            report.write(args.fault_report)
        return 1
    elapsed = time.time() - t0
    seeds = np.asarray(res.seeds)
    spread = float(influence(g, seeds, jax.random.fold_in(key, 99),
                             model=args.model, num_sims=args.eval_sims,
                             engine=args.eval_engine))
    lost = m - len(survivors)
    print(f"[im] survivors={len(survivors)}/{m} (lost {lost}) "
          f"alpha={alpha0}->{alpha_used} "
          f"coverage={int(res.coverage)} spread={spread:.1f} "
          f"({100 * spread / n:.2f}% of graph) in {elapsed:.2f}s")
    report.add_events(plan)
    report.check("round_survived", True, survivors=len(survivors),
                 lost=lost, coverage=int(res.coverage),
                 spread=spread, alpha_used=alpha_used,
                 straggler_flags=monitor.flags)
    if args.fault_report:
        report.write(args.fault_report)
        print(f"[im] fault report -> {args.fault_report}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
