import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  1. FULL model (scan-over-layers) lower+compile on the production
     mesh -> compile success + memory_analysis (bytes/device) +
     top-level collective schedule.         [deliverable (e)]
  2. PROBE models (unrolled, small per-stack layer counts) ->
     cost_analysis + parsed collective bytes, linearly extrapolated to
     the full depth -> the three roofline terms. [deliverable (g)]

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
      --shape train_4k [--multi-pod] [--skip-probes] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --im   # GreediRIS round

The GreediRIS cells lower the paper's distributed round itself
(sampling + all_to_all + local greedy + streaming aggregation) at
m=256 and m=512 machines, plus the Ripples baseline (k psums) so the
communication reduction is measurable from the compiled HLO.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.distributed import hlo_analysis as hlo
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.models import model as model_lib
from repro.models import transformer as tfm
from repro.optim import adamw


def _named(mesh, tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        tree, is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape: str, multi_pod: bool, *,
               cfg_override=None, scan_layers: bool = True):
    """Lower + compile one cell; returns (compiled, mesh, meta)."""
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    cell = SHAPES[shape]
    cfg = cfg_override or get_config(arch)
    cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    # >=100B params: bf16 optimizer moments (production choice for HBM
    # fit; recorded in EXPERIMENTS.md §Dry-run)
    from repro.configs import param_count
    big = param_count(cfg) > 100e9
    opt_cfg = adamw.OptConfig(state_dtype="bfloat16" if big else "float32")
    bundle = model_lib.build(cfg, opt_cfg, multi_pod=multi_pod)
    dp_size = int(np.prod([mesh.shape[a] for a in bundle.rules["dp"]]))
    bundle.rules = dict(bundle.rules)

    with jax.set_mesh(mesh):
        if cell.kind == "train":
            state_sds, specs = specs_lib.state_shapes(cfg, opt_cfg)
            state_ps = bundle.state_pspecs(specs)
            state_ps = model_lib.concretize_pspecs(state_ps, state_sds,
                                                   mesh)
            batch_sds, batch_ps = specs_lib.batch_specs(
                cfg, cell, bundle.rules, dp_size)
            step = bundle.train_step()
            lowered = jax.jit(
                step, in_shardings=(_named(mesh, state_ps),
                                    _named(mesh, batch_ps)),
                out_shardings=(_named(mesh, state_ps), None),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds)
        elif cell.kind == "prefill":
            state_sds, specs = specs_lib.state_shapes(cfg, opt_cfg)
            params_sds = state_sds.params
            params_ps = model_lib.concretize_pspecs(
                bundle.param_pspecs(specs), params_sds, mesh)
            batch_sds, batch_ps = specs_lib.batch_specs(
                cfg, cell, bundle.rules, dp_size)
            step = bundle.prefill_step(max_len=cell.seq_len + 128)
            lowered = jax.jit(
                step, in_shardings=(_named(mesh, params_ps),
                                    _named(mesh, batch_ps)),
            ).lower(params_sds, batch_sds)
        else:  # decode
            state_sds, specs = specs_lib.state_shapes(cfg, opt_cfg)
            params_sds = state_sds.params
            params_ps = model_lib.concretize_pspecs(
                bundle.param_pspecs(specs), params_sds, mesh)
            (carry, tok, pos), (carry_ps, tok_ps, pos_ps) = \
                specs_lib.decode_args(cfg, bundle, cell, bundle.rules,
                                      dp_size)
            carry_ps = model_lib.concretize_pspecs(carry_ps, carry, mesh)
            step = bundle.decode_step()
            lowered = jax.jit(
                step, in_shardings=(_named(mesh, params_ps),
                                    _named(mesh, carry_ps),
                                    NamedSharding(mesh, tok_ps),
                                    NamedSharding(mesh, pos_ps)),
                donate_argnums=(1,),
            ).lower(params_sds, carry, tok, pos)
        compiled = lowered.compile()
    return compiled, mesh, {"cell": cell, "cfg": cfg}


def probe_costs(arch: str, shape: str, multi_pod: bool):
    return probe_costs_cfg(arch, shape, multi_pod, get_config(arch))


def probe_costs_cfg(arch: str, shape: str, multi_pod: bool, cfg):
    """Extract per-stack unit costs from unrolled probes and
    extrapolate to full depth.  Returns dict of extrapolated
    (flops, bytes, link_bytes) per device.

    Pure-SSM prefill at 32k+ would unroll S/chunk (512+) scan bodies
    per probe layer — prohibitive compile time.  Since every SSD cost
    component is exactly linear in S, those probes lower at seq 4096
    and scale the totals by S/4096 (exact; noted in EXPERIMENTS)."""
    cell = SHAPES[shape]
    seq_scale = 1.0
    if (cfg.family == "ssm" and cell.kind == "prefill"
            and cell.seq_len > 8192):
        seq_scale = cell.seq_len / 4096.0
        shape = shape + "@4k"
        SHAPES[shape] = dataclasses.replace(cell, name=shape,
                                            seq_len=4096)
    big = 1 << 30   # single-block flash attention: exact flop counting
    if cfg.is_encoder_decoder:
        counts = [cfg.encoder_layers, cfg.num_layers]

        def probe_cfg(c):
            return dataclasses.replace(cfg, encoder_layers=c[0],
                                       num_layers=c[1], scan_layers=False,
                                       remat=False, q_chunk=big,
                                       kv_chunk=big)
    else:
        plan = tfm.build_plan(cfg)
        counts = [count for _, count in plan]

        def probe_cfg(c):
            override = tuple(
                (unit, ci) for (unit, _), ci in zip(plan, c))
            return dataclasses.replace(cfg, plan_override=override,
                                       scan_layers=False, remat=False,
                                       q_chunk=big, kv_chunk=big)

    base = [1] * len(counts)
    probes = [base] + [
        [1 + (1 if j == i else 0) for j in range(len(counts))]
        for i in range(len(counts))]

    results = []
    for c in probes:
        compiled, _, _ = lower_cell(arch, shape, multi_pod,
                                    cfg_override=probe_cfg(c),
                                    scan_layers=False)
        cost = hlo.cost_summary(compiled)
        coll = hlo.parse_collectives(compiled.as_text())
        results.append((cost["flops"], cost["bytes"],
                        coll.total_link_bytes))
        del compiled

    base_cost = np.array(results[0])
    unit_costs = [np.array(results[1 + i]) - base_cost
                  for i in range(len(counts))]
    fixed = base_cost - sum(unit_costs)          # embed/head/opt overhead
    total = fixed + sum(u * c for u, c in zip(unit_costs, counts))
    total = np.maximum(total, 0.0) * seq_scale
    return {
        "flops": float(total[0]), "bytes": float(total[1]),
        "link_bytes": float(total[2]),
        "probe_fixed": [float(x) for x in fixed],
        "probe_units": [[float(x) for x in u] for u in unit_costs],
        "stack_counts": counts,
    }


def run_cell(arch: str, shape: str, multi_pod: bool,
             skip_probes: bool = False) -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    compiled, mesh, meta = lower_cell(arch, shape, multi_pod)
    rec["memory"] = hlo.memory_summary(compiled)
    coll_full = hlo.parse_collectives(compiled.as_text())
    rec["collectives_top_level"] = {
        "bytes_by_op": coll_full.bytes_by_op, "count": coll_full.count}
    rec["compile_s"] = round(time.time() - t0, 1)
    print(f"[dryrun] {arch} x {shape} x {rec['mesh']}: compiled in "
          f"{rec['compile_s']}s; peak {rec['memory']['peak_bytes']/2**30:.2f} "
          f"GiB/dev; args {rec['memory']['argument_bytes']/2**30:.2f} GiB/dev",
          flush=True)
    del compiled

    if not skip_probes:
        from repro.distributed import memory_model
        t1 = time.time()
        probe = probe_costs(arch, shape, multi_pod)
        rec["probe"] = probe
        cfg = meta["cfg"]
        cell = meta["cell"]
        dp = 32 if multi_pod else 16
        n_dev = 512 if multi_pod else 256
        mem_bytes = memory_model.hbm_traffic(cfg, cell, n_dev=n_dev,
                                             dp=dp, tp=16,
                                             remat=cfg.remat)
        terms = hlo.roofline(probe["flops"], mem_bytes,
                             probe["link_bytes"])
        mflops = memory_model.model_flops(cfg, cell)
        rec["roofline"] = {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "memory_s_hlo": probe["bytes"] / hlo.HBM_BW,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops": mflops,
            "useful_flops_frac": mflops / max(probe["flops"] * n_dev, 1.0),
            "bound_s": terms.bound_s,
        }
        rec["probe_s"] = round(time.time() - t1, 1)
        print(f"[dryrun]   roofline: compute {terms.compute_s:.4f}s "
              f"memory {terms.memory_s:.4f}s (hlo "
              f"{rec['roofline']['memory_s_hlo']:.4f}s) collective "
              f"{terms.collective_s:.4f}s -> {terms.dominant}-bound; "
              f"useful-flops {rec['roofline']['useful_flops_frac']:.2f}",
              flush=True)
    return rec


# ------------------------- GreediRIS dry-run -------------------------

def run_im_cell(multi_pod: bool, *, n: int = 4_800_000, theta: int = 1 << 20,
                k: int = 100, d_pad: int = 32, alpha: float = 0.125,
                aggregate: str = "gather", baseline: bool = False,
                shuffle: str = "dense", est_rrr_len: float = 16.0) -> dict:
    """Lower + compile the distributed GreediRIS round (or the Ripples
    k-reduction baseline) at production scale: LiveJournal-sized graph
    (n=4.8M), theta=2^20 samples, k=100 seeds."""
    from repro.core import greediris
    m_total = 512 if multi_pod else 256
    mesh = mesh_lib.make_im_mesh(m_total, multi_pod=multi_pod)
    axes = ("pod", "machines") if multi_pod else ("machines",)
    sds = jax.ShapeDtypeStruct
    nbr = sds((n, d_pad), jnp.int32)
    prob = sds((n, d_pad), jnp.float32)
    wt = sds((n, d_pad), jnp.float32)
    key = sds((2,), jnp.uint32)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if baseline:
            fn, _ = greediris.build_ripples_round(
                mesh, axes, n=n, theta=theta, k=k, sample_chunks=8,
                unroll_k=True)
        else:
            fn, _, _ = greediris.build_round(
                mesh, axes, n=n, theta=theta, k=k, max_degree=d_pad,
                alpha_trunc=alpha, aggregate=aggregate, sample_chunks=8,
                shuffle=shuffle, est_rrr_len=est_rrr_len)
        rep = NamedSharding(mesh, P())
        lowered = jax.jit(fn, in_shardings=(rep, rep, rep, rep)).lower(
            nbr, prob, wt, key)
        compiled = lowered.compile()
    name = "ripples-baseline" if baseline else \
        f"greediris-{aggregate}-{shuffle}-a{alpha}"
    rec = {"arch": f"greediris:{name}", "shape": f"n{n}-theta{theta}-k{k}",
           "mesh": "2x256" if multi_pod else "256",
           "memory": hlo.memory_summary(compiled),
           "compile_s": round(time.time() - t0, 1)}
    coll = hlo.parse_collectives(compiled.as_text())
    rec["collectives_top_level"] = {
        "bytes_by_op": coll.bytes_by_op, "count": coll.count,
        "total_link_bytes": coll.total_link_bytes}
    cost = hlo.cost_summary(compiled)
    rec["cost"] = cost
    print(f"[dryrun] {rec['arch']} x {rec['mesh']}: compiled in "
          f"{rec['compile_s']}s; peak {rec['memory']['peak_bytes']/2**30:.2f}"
          f" GiB/dev; coll {coll.total_link_bytes/2**20:.1f} MiB/dev",
          flush=True)
    del compiled
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--im", action="store_true",
                    help="GreediRIS distributed-round dry-run")
    ap.add_argument("--im-baseline", action="store_true")
    ap.add_argument("--im-aggregate", default="gather")
    ap.add_argument("--im-alpha", type=float, default=0.125)
    ap.add_argument("--im-n", type=int, default=4_800_000)
    ap.add_argument("--im-theta", type=int, default=1 << 20)
    ap.add_argument("--im-shuffle", default="dense",
                    choices=("dense", "sparse"))
    ap.add_argument("--im-rrr-len", type=float, default=16.0)
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    def flush(recs):
        if not args.out:
            return
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        existing.extend(recs)
        with open(args.out, "w") as f:
            json.dump(existing, f, indent=1)
        recs.clear()

    records = []
    if args.im:
        records.append(run_im_cell(
            args.multi_pod, n=args.im_n, theta=args.im_theta,
            alpha=args.im_alpha, aggregate=args.im_aggregate,
            baseline=args.im_baseline, shuffle=args.im_shuffle,
            est_rrr_len=args.im_rrr_len))
    elif args.all:
        failed = False
        for arch in ARCHS:
            cfg = get_config(arch)
            for shape in list(SHAPES):
                if "@" in shape or not applicable(cfg, shape):
                    continue
                for mp in (False, True):
                    try:
                        # roofline probes: single-pod only (the roofline
                        # table is single-pod per EXPERIMENTS §Roofline)
                        records.append(run_cell(
                            arch, shape, mp,
                            skip_probes=args.skip_probes or mp))
                    except Exception as e:
                        traceback.print_exc()
                        failed = True
                        records.append({"arch": arch, "shape": shape,
                                        "mesh": "2x16x16" if mp else
                                        "16x16", "error": str(e)})
                    flush(records)
        return 1 if failed else 0
    else:
        records.append(run_cell(args.arch, args.shape, args.multi_pod,
                                args.skip_probes))

    flush(records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
