"""Spread gate: measured-quality scenario harness (paper §4).

Every solver (S3) and sampler (S1) variant in this repo is *supposed*
to be bit-identical to the scan/dense reference — the parity tests pin
that on coverage words.  This harness closes the remaining gap: it
gates on the quantity the paper actually reports, the **measured
spread** of the returned seed set under Monte-Carlo cascade simulation
(:mod:`repro.core.cascade`).  A k-sweep runs every solver x sampler
variant end-to-end (sample RRR incidence -> greedy max-k-cover ->
simulate the chosen seeds) and asserts each variant's per-simulation
activation counts are statistically indistinguishable from the
reference via a paired z-test — for today's bit-identical variants the
paired differences are exactly zero; a future variant that trades
exactness for speed gets a real significance test instead of a
guaranteed failure.

A GreediRIS (RandGreedi + streaming aggregator) row rides along: its
seeds legitimately differ from greedy's, so it gets a quality *floor*
(measured spread >= ``QUALITY_FLOOR`` x reference) rather than a
z-test, plus the internal consistency check that the returned winning
cover (``RandGreediResult.covered``) popcounts to its reported
coverage.

Run directly (exits 1 on any gate failure)::

    PYTHONPATH=src python -m benchmarks.spread_gate --fast

The CI bench job runs it, so a quality regression fails that job.
"""
from __future__ import annotations

import argparse
import json
import math

import jax
import numpy as np

from repro.core import bitset, cascade, maxcover, randgreedi
from repro.core.rrr import sample_incidence
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency, padded_forward_adjacency

# The reference pipeline every variant is measured against.
REFERENCE = ("scan", "dense")
# (solver, sampler) variants under gate — each exercises a different
# kernelized path of the stack.
VARIANTS = (
    ("fused", "dense"),
    ("resident", "packed"),
    ("lazy", "packed"),
    ("lazy", "kernel"),
)
Z_MAX = 4.0            # paired z-test threshold (|z| above this fails)
QUALITY_FLOOR = 0.5    # GreediRIS spread >= floor * reference spread


def _paired_z(counts: np.ndarray, ref: np.ndarray) -> float:
    """Paired z statistic of per-simulation activation counts vs the
    reference (same eval key ⇒ same coins ⇒ a paired comparison).
    0.0 when bit-identical; inf on a constant nonzero shift."""
    d = counts.astype(np.float64) - ref.astype(np.float64)
    if not d.any():
        return 0.0
    sd = float(d.std(ddof=1))
    if sd == 0.0:
        return math.inf
    return abs(float(d.mean())) / (sd / math.sqrt(d.size))


def run_gate(*, n: int = 512, avg_deg: float = 6.0, ks=(4, 8, 16),
             theta: int = 1024, num_sims: int = 64, max_steps: int = 32,
             model: str = "IC", eval_engine: str = "packed",
             z_max: float = Z_MAX, seed: int = 0, m: int = 2,
             quiet: bool = False):
    """Run the k-sweep; returns ``(ok, rows)`` where rows is a list of
    dicts (one per variant per k, plus the GreediRIS rows)."""
    g = generators.erdos_renyi(n, avg_deg, seed=seed)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    key = jax.random.key(seed)
    eval_key = jax.random.fold_in(key, 99)

    def say(msg):
        if not quiet:
            print(msg, flush=True)

    # One incidence per sampler (same key: dense/packed/kernel are
    # bit-identical, but the gate measures each variant's own path
    # end-to-end rather than assuming that).
    samplers = {REFERENCE[1]} | {s for _, s in VARIANTS}
    incidence = {
        s: sample_incidence(nbr, prob, wt, key, theta=theta, n=n,
                            model=model, max_steps=max_steps, sampler=s,
                            fwd=(None if s == "dense" else fwd))
        for s in sorted(samplers)}

    def measure(seeds):
        return np.asarray(cascade.cascade_counts(
            g, np.asarray(seeds), eval_key, model=model,
            num_sims=num_sims, max_steps=max_steps, engine=eval_engine))

    ok = True
    rows = []
    for k in ks:
        ref_sol = maxcover.greedy_maxcover(incidence[REFERENCE[1]], k,
                                           solver=REFERENCE[0])
        ref_counts = measure(ref_sol.seeds)
        ref_spread = float(ref_counts.mean())
        say(f"[gate] k={k} reference {REFERENCE[0]}+{REFERENCE[1]} "
            f"spread={ref_spread:.2f}")
        for solver, sampler in VARIANTS:
            sol = maxcover.greedy_maxcover(incidence[sampler], k,
                                           solver=solver)
            counts = measure(sol.seeds)
            z = _paired_z(counts, ref_counts)
            passed = z <= z_max
            ok &= passed
            rows.append({
                "name": f"spread_gate/{solver}+{sampler}/k={k}",
                "spread": float(counts.mean()),
                "ref_spread": ref_spread, "z": z,
                "identical": bool((counts == ref_counts).all()),
                "pass": passed,
            })
            say(f"[gate]   {solver}+{sampler}: "
                f"spread={float(counts.mean()):.2f} z={z:.2f} "
                f"{'ok' if passed else 'FAIL'}")

        # GreediRIS quality floor + winning-cover consistency.
        res = randgreedi.randgreedi_maxcover(
            incidence[REFERENCE[1]], key, m=m, k=k,
            aggregator="streaming")
        cov_pop = int(np.sum(np.asarray(bitset.popcount(res.covered))))
        cov_ok = cov_pop == int(res.coverage)
        gr_counts = measure(res.seeds)
        gr_spread = float(gr_counts.mean())
        floor_ok = gr_spread >= QUALITY_FLOOR * ref_spread
        ok &= cov_ok and floor_ok
        rows.append({
            "name": f"spread_gate/greediris_m{m}/k={k}",
            "spread": gr_spread, "ref_spread": ref_spread,
            "covered_popcount": cov_pop, "coverage": int(res.coverage),
            "pass": cov_ok and floor_ok,
        })
        say(f"[gate]   greediris(m={m}): spread={gr_spread:.2f} "
            f"(floor {QUALITY_FLOOR:.2f}x) covered_popcount={cov_pop} "
            f"{'ok' if cov_ok and floor_ok else 'FAIL'}")
    return ok, rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized sweep")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write per-variant rows to OUT as JSON")
    ap.add_argument("--z", type=float, default=Z_MAX,
                    help="paired z-test failure threshold")
    ap.add_argument("--n", type=int, default=0,
                    help="override graph size (0 = preset)")
    ap.add_argument("--sims", type=int, default=0,
                    help="override eval simulations (0 = preset)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    preset = (dict(n=256, avg_deg=6.0, ks=(4, 8), theta=512,
                   num_sims=64)
              if args.fast else
              dict(n=512, avg_deg=6.0, ks=(4, 8, 16), theta=1024,
                   num_sims=128))
    if args.n:
        preset["n"] = args.n
    if args.sims:
        preset["num_sims"] = args.sims
    ok, rows = run_gate(z_max=args.z, seed=args.seed, **preset)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"pass": ok, "rows": rows}, f, indent=2,
                      sort_keys=True)
            f.write("\n")
    print(f"[gate] {'PASS' if ok else 'FAIL'} "
          f"({sum(r['pass'] for r in rows)}/{len(rows)} rows)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
