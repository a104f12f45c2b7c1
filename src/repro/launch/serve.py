"""Online influence service driver: replay a query trace against the
resident sketch pool (``repro.core.service``).

  PYTHONPATH=src python -m repro.launch.serve --n 256 --queries 16 \
      --batch 8 --solver resident --check

Generates a deterministic trace of (k, seed-constraint, budget)
queries, admits them in batches of ``--batch`` through
:class:`~repro.core.service.InfluenceService` (ONE batched solve per
batch over the shared pool), and reports throughput.  ``--check``
additionally replays every query through the sequential
``answer_one`` reference and exits non-zero unless the batched answers
are bit-identical — the serve smoke gate CI runs.  ``--refresh-every``
forces a pool refresh between batches so the replay also exercises the
generation-drain path (tickets admitted before the refresh complete on
their old generation's pool).

Supervised replay (the CI ``chaos`` job)
----------------------------------------
``--recover`` switches to the supervised mode: the pool is
snapshotted to a :class:`~repro.checkpoint.store.CheckpointStore`
before every batch, faults from ``--inject site:kind[:at[:arg]]``
specs fire deterministically mid-replay, and a fault that outlives
the retry budget escalates to restore-from-snapshot + re-answer.
``--kill-after N`` stops after N batches (a killed replay, snapshots
left behind); ``--resume-from N`` restores the newest snapshot and
resumes the trace at batch N.  With ``--check``, the faulty/resumed
answers are compared bit-for-bit against a clean full replay of the
same schedule; ``--fault-report`` writes the JSON artifact.
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.core import service as svc
from repro.core.service import (InfluenceService, Query,
                                answer_with_retry, restore_pool,
                                snapshot_pool)
from repro.launch.im_driver import make_graph
from repro.runtime import faults
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.faults import FaultPlan, InjectedFault


def make_trace(n: int, num_queries: int, seed: int,
               *, k_max: int = 8, excl_max: int = 6,
               budget_frac: float = 0.25) -> list[Query]:
    """Deterministic query trace: mixed k, mixed-length exclusion
    sets (seed-constraints), and a sprinkle of spread budgets."""
    rng = np.random.default_rng(seed)
    trace = []
    for _ in range(num_queries):
        k = int(rng.integers(1, k_max + 1))
        e = int(rng.integers(0, excl_max + 1))
        excluded = tuple(int(v) for v in
                         rng.choice(n, size=e, replace=False)) if e else ()
        budget = (float(rng.uniform(1.0, budget_frac * n))
                  if rng.random() < 0.3 else None)
        trace.append(Query(k=k, excluded=excluded, budget=budget))
    return trace


def replay(service: InfluenceService, trace: list[Query], *,
           batch: int, refresh_every: int = 0):
    """Admit and answer the trace in batches.  Returns
    (answers, pools-by-generation, elapsed seconds).  With
    ``refresh_every`` > 0, a refresh is injected after every that-many
    batches WITH the next batch's tickets already admitted — the
    in-flight tickets then drain on their old generation.  The pool
    snapshot dict keeps every generation that answered alive for the
    ``--check`` replay (the service itself retires drained pools)."""
    answers = []
    pools = {}
    t0 = time.time()
    for i in range(0, len(trace), batch):
        tickets = [service.admit(q) for q in trace[i:i + batch]]
        if refresh_every and (i // batch + 1) % refresh_every == 0 \
                and service.pool.theta < service.max_theta:
            service.refresh()          # tickets drain on the old tag
        for t in tickets:
            pools[t.generation] = service._pools[t.generation]
        answers.extend(service.answer(tickets))
    return answers, pools, time.time() - t0


def check_bit_identity(service: InfluenceService, pools: dict,
                       trace: list[Query], answers: list) -> int:
    """Replay each query through the sequential ``answer_one``
    reference on the generation that answered it (``pools`` holds the
    snapshot — the service may have retired drained generations);
    count mismatches."""
    mismatches = 0
    for q, a in zip(trace, answers):
        ref = svc.answer_one(pools[a.generation], q,
                             solver=service.solver,
                             delta=service.delta, alpha=service.alpha)
        same = (np.array_equal(a.seeds, ref.seeds)
                and a.k_used == ref.k_used
                and a.coverage == ref.coverage
                and a.sigma_lower == ref.sigma_lower
                and a.sigma_upper == ref.sigma_upper)
        if not same:
            mismatches += 1
            print(f"[serve] MISMATCH k={q.k} excluded={q.excluded} "
                  f"budget={q.budget}: batched seeds={a.seeds} "
                  f"cov={a.coverage} vs sequential seeds={ref.seeds} "
                  f"cov={ref.coverage}", file=sys.stderr)
    return mismatches


# ---------------------------------------------------------------------
# Supervised replay: snapshot / inject / recover / resume
# ---------------------------------------------------------------------

def _snapshot_with_retry(store: CheckpointStore, pool, *, retries: int,
                         backoff_s: float, sleep_fn) -> int:
    """Blocking snapshot with bounded retry: an injected (or real)
    write failure is acknowledged via ``clear_error`` and the write
    retried — a recovery point must not fail silently."""
    last: Optional[Exception] = None
    for attempt in range(retries + 1):
        if attempt and backoff_s:
            sleep_fn(backoff_s * (2 ** (attempt - 1)))
        try:
            return snapshot_pool(store, pool)
        except (InjectedFault, OSError) as e:
            store.clear_error()
            last = e
    raise last  # type: ignore[misc]


def _admit_with_retry(service: InfluenceService, queries, *,
                      retries: int, backoff_s: float, sleep_fn):
    """Admit a batch, releasing partial admissions and retrying on an
    injected admit fault (the site fires before any in-flight count is
    taken for the failing query, so a retry is exact)."""
    last: Optional[Exception] = None
    for attempt in range(retries + 1):
        if attempt and backoff_s:
            sleep_fn(backoff_s * (2 ** (attempt - 1)))
        tickets = []
        try:
            for q in queries:
                tickets.append(service.admit(q))
            return tickets
        except InjectedFault as e:
            service.release(tickets)
            last = e
    raise last  # type: ignore[misc]


def supervised_replay(g, key, trace: list[Query], *, batch: int,
                      store: CheckpointStore,
                      plan: Optional[FaultPlan] = None,
                      refresh_every: int = 0, retries: int = 2,
                      backoff_s: float = 0.0, sleep_fn=time.sleep,
                      start_batch: int = 0, stop_after: int = 0,
                      theta0: int = 512, max_theta: int = 1 << 12,
                      slab: int = 256, solver: str = "resident",
                      model: str = "IC", sampler: str = "dense"):
    """Replay ``trace`` under supervision: snapshot before every
    batch, retry transient faults, restore-from-snapshot when the
    retry budget is exhausted.

    The batch loop is ``refresh (scheduled) -> snapshot -> admit ->
    answer``; with ``start_batch`` > 0 the newest snapshot (written by
    the batch before the kill point) is restored and the loop resumes
    mid-trace — because snapshots capture the full salted-slab PRNG
    state, the remaining answers are bit-identical to an uninterrupted
    replay (asserted by ``--check`` / the chaos gate).  ``stop_after``
    bounds the number of batches processed (the "kill").

    Returns ``(answers, service, stats)`` with
    ``stats = {"recoveries": .., "batches": ..}``.
    """
    num_batches = (len(trace) + batch - 1) // batch
    end = (min(num_batches, start_batch + stop_after) if stop_after
           else num_batches)
    if start_batch == 0:
        service = InfluenceService(
            g, key, theta0=theta0, max_theta=max_theta, slab=slab,
            solver=solver, model=model, sampler=sampler,
            fault_plan=plan)
    else:
        pool, step = restore_pool(store, g)
        if pool is None:
            raise FileNotFoundError(
                f"--resume-from {start_batch} but no snapshot in "
                f"{store.root}")
        service = InfluenceService.from_pool(
            pool, theta0=theta0, max_theta=max_theta, solver=solver,
            fault_plan=plan)
    answers: list = []
    recoveries = 0
    for bi in range(start_batch, end):
        queries = trace[bi * batch:(bi + 1) * batch]
        do_refresh = bool(refresh_every and bi
                          and bi % refresh_every == 0)
        for attempt in (0, 1):
            try:
                if do_refresh and service.pool.theta < service.max_theta:
                    service.refresh()
                do_refresh = False
                if service.pool.theta:
                    _snapshot_with_retry(store, service.pool,
                                         retries=retries,
                                         backoff_s=backoff_s,
                                         sleep_fn=sleep_fn)
                tickets = _admit_with_retry(service, queries,
                                            retries=retries,
                                            backoff_s=backoff_s,
                                            sleep_fn=sleep_fn)
                answers.extend(answer_with_retry(
                    service, tickets, retries=retries,
                    backoff_s=backoff_s, sleep_fn=sleep_fn))
                break
            except (InjectedFault, svc.StaleGenerationError):
                # Retry budget exhausted -> escalate: rebuild the
                # service from the newest snapshot and re-answer the
                # batch (deterministic, so the recovered answers match
                # the clean replay bit-for-bit).
                if attempt:
                    raise
                pool, _ = restore_pool(store, g)
                if pool is None:
                    raise
                service = InfluenceService.from_pool(
                    pool, theta0=theta0, max_theta=max_theta,
                    solver=solver, fault_plan=plan)
                recoveries += 1
    return answers, service, {"recoveries": recoveries,
                              "batches": end - start_batch}


def answers_equal(a, b) -> bool:
    """Bit-identity of two :class:`~repro.core.service.Answer`s —
    seeds arrays plus every scalar field (floats compared exactly)."""
    return bool(np.array_equal(a.seeds, b.seeds) and a[1:] == b[1:])


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="er", choices=("er", "ba", "rmat"))
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--avg-deg", type=float, default=6.0)
    ap.add_argument("--model", default="IC", choices=("IC", "LT"))
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8,
                    help="concurrent queries per batched solve")
    ap.add_argument("--k-max", type=int, default=8)
    ap.add_argument("--solver", default="resident",
                    choices=("scan", "fused", "resident", "lazy"))
    ap.add_argument("--sampler", default="dense",
                    choices=("dense", "packed", "kernel"))
    ap.add_argument("--theta0", type=int, default=512)
    ap.add_argument("--max-theta", type=int, default=1 << 12)
    ap.add_argument("--slab", type=int, default=256)
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="refresh the pool after every N batches, with "
                         "that batch's tickets draining on the old "
                         "generation (0 = never)")
    ap.add_argument("--check", action="store_true",
                    help="replay every query through the sequential "
                         "answer_one reference and exit non-zero on "
                         "any batched-vs-sequential mismatch (the CI "
                         "serve smoke gate); with --recover, compare "
                         "the supervised answers bit-for-bit against "
                         "a clean full replay instead")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject", action="append", default=[],
                    type=faults.cli_fault_arg,
                    metavar="SITE:KIND[:AT[:ARG]]",
                    help="inject a deterministic fault (repeatable); "
                         f"sites: {', '.join(faults.SITES)}; kinds: "
                         f"{', '.join(faults.FAULT_KINDS)}. "
                         "Requires --recover.")
    ap.add_argument("--recover", action="store_true",
                    help="supervised replay: snapshot the pool before "
                         "every batch and restore+re-answer when a "
                         "fault outlives the retry budget")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory for --recover "
                         "(default: a fresh temp dir)")
    ap.add_argument("--kill-after", type=int, default=0,
                    help="process only this many batches then stop — "
                         "a killed replay; snapshots stay in "
                         "--ckpt-dir for --resume-from")
    ap.add_argument("--resume-from", type=int, default=0,
                    help="restore the newest snapshot from --ckpt-dir "
                         "and resume the trace at this batch index")
    ap.add_argument("--retries", type=int, default=2,
                    help="per-stage retry budget in supervised mode")
    ap.add_argument("--backoff", type=float, default=0.0,
                    help="base retry backoff seconds (doubles per "
                         "attempt)")
    ap.add_argument("--fault-report", default=None, metavar="PATH",
                    help="write the JSON fault report (fired events + "
                         "named checks) to PATH")
    args = ap.parse_args(argv)

    # Cross-flag validation at the argparse boundary (SystemExit 2
    # with an actionable message, not a deep failure mid-replay).
    if args.inject and not args.recover:
        ap.error("--inject requires --recover (the supervised replay "
                 "is what recovers from the injected faults)")
    if (args.kill_after or args.resume_from) and not args.recover:
        ap.error("--kill-after/--resume-from require --recover")
    if args.kill_after < 0 or args.resume_from < 0:
        ap.error("--kill-after/--resume-from must be >= 0")
    if args.resume_from and not args.ckpt_dir:
        ap.error("--resume-from needs --ckpt-dir (the directory the "
                 "killed replay left its snapshots in)")
    if args.retries < 0:
        ap.error("--retries must be >= 0")

    g = make_graph(args.graph, args.n, args.avg_deg, args.seed)
    trace = make_trace(g.num_vertices, args.queries, args.seed + 1,
                       k_max=args.k_max)
    if args.recover:
        return _main_supervised(args, g, trace)
    service = InfluenceService(
        g, jax.random.PRNGKey(args.seed), theta0=args.theta0,
        max_theta=args.max_theta, slab=args.slab, solver=args.solver,
        model=args.model, sampler=args.sampler)
    print(f"[serve] graph n={g.num_vertices} m={g.num_edges} "
          f"solver={args.solver} trace={len(trace)} queries "
          f"(batch={args.batch})")

    answers, pools, elapsed = replay(service, trace, batch=args.batch,
                                     refresh_every=args.refresh_every)
    gens = sorted({a.generation for a in answers})
    certified = sum(a.certified for a in answers)
    state = svc.per_query_state_bytes(service.pool.words, args.k_max,
                                      max(len(q.excluded) for q in trace))
    print(f"[serve] {len(answers)} answers in {elapsed:.2f}s "
          f"({len(answers) / max(elapsed, 1e-9):.1f} queries/s)  "
          f"generations={gens} theta={service.pool.theta} "
          f"certified={certified}/{len(answers)} "
          f"per-query-state={state}B")

    if args.check:
        bad = check_bit_identity(service, pools, trace, answers)
        if bad:
            print(f"[serve] FAIL: {bad}/{len(trace)} batched answers "
                  f"differ from the sequential reference",
                  file=sys.stderr)
            return 1
        print(f"[serve] check OK: all {len(trace)} batched answers "
              f"bit-identical to the sequential reference")
    return 0


def _main_supervised(args, g, trace) -> int:
    """The --recover path: supervised replay under the injected fault
    plan, optional kill/resume, clean-replay bit-identity check, and
    the JSON fault report."""
    plan = FaultPlan(args.inject) if args.inject else None
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_ckpt_")
    cfg = dict(batch=args.batch, refresh_every=args.refresh_every,
               theta0=args.theta0, max_theta=args.max_theta,
               slab=args.slab, solver=args.solver, model=args.model,
               sampler=args.sampler)
    print(f"[serve] supervised replay: {len(args.inject)} fault "
          f"spec(s), ckpt={ckpt}, resume_from={args.resume_from}, "
          f"kill_after={args.kill_after or 'never'}")
    answers, service, stats = supervised_replay(
        g, jax.random.PRNGKey(args.seed), trace,
        store=CheckpointStore(ckpt, fault_plan=plan), plan=plan,
        retries=args.retries, backoff_s=args.backoff,
        start_batch=args.resume_from, stop_after=args.kill_after, **cfg)
    fired = len(plan.events) if plan else 0
    print(f"[serve] {len(answers)} answers over {stats['batches']} "
          f"batch(es); {fired} fault(s) fired, "
          f"{stats['recoveries']} restore-from-snapshot "
          f"recover(ies); theta={service.pool.theta} "
          f"generation={service.generation}")

    report = faults.FaultReport()
    report.add_events(plan)
    report.check("replay_completed", True, answers=len(answers),
                 recoveries=stats["recoveries"], fired=fired)
    bad = 0
    if args.check:
        # Clean reference: a full uninterrupted replay of the same
        # schedule, no faults, throwaway snapshot dir.  The supervised
        # answers (a slice when killed/resumed) must match bit-for-bit.
        with tempfile.TemporaryDirectory() as d:
            ref, _, _ = supervised_replay(
                g, jax.random.PRNGKey(args.seed), trace,
                store=CheckpointStore(d), plan=None, **cfg)
        lo = args.resume_from * args.batch
        ref_slice = ref[lo:lo + len(answers)]
        bad = sum(not answers_equal(a, b)
                  for a, b in zip(answers, ref_slice))
        bad += abs(len(answers) - len(ref_slice))
        report.check("bit_identity_vs_clean_replay", bad == 0,
                     mismatches=bad, compared=len(ref_slice))
        if bad:
            print(f"[serve] FAIL: {bad}/{len(ref_slice)} supervised "
                  f"answers differ from the clean replay",
                  file=sys.stderr)
        else:
            print(f"[serve] check OK: all {len(ref_slice)} supervised "
                  f"answers bit-identical to the clean replay")
    if args.fault_report:
        report.write(args.fault_report)
        print(f"[serve] fault report -> {args.fault_report}")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
