"""Kernel contract registry: each Pallas family's launch/memory/layout
invariants, declared once and proved by tracing canonical fixtures.

A :class:`KernelContract` binds together

  * a *declaration* — the invariants that live next to the kernel
    source (module-level ``CONTRACT`` dicts in
    ``kernels/rrr_expand.py``, ``kernels/greedy_pick.py``,
    ``kernels/lazy_greedy.py``, ``kernels/bucket_insert.py``,
    ``core/cascade.py``, ``core/service.py``): exact ``pallas_call``
    count, whether the launch sits inside a loop body, the dtype
    whitelist, and the donation/aliasing expectation;
  * a *fixture* — a canonical abstract shape to trace it on, built
    here (small graphs/pools sized so tracing is fast but every
    geometry knob — padding, d-tiling, heavy hubs — is exercised);
  * *layout patterns* — intermediates that must or must not appear
    (the resident sampler's forbidden ``[n, d_out, W]`` gmask, the
    streamed layout's required one).

:func:`run_contract` traces the fixture with ``jax.make_jaxpr`` and
checks everything structurally via :mod:`repro.analysis.jaxpr_check`;
the VMEM footprint summed from the launch's block specs is checked
against the same ``kernels.vmem_budget.budget_bytes()`` the "auto"
policies solve under, so a kernel whose scratch outgrows the model
fails the checker before it ever overflows on hardware.  An optional
HLO pass compiles the fixture and flags collectives that have no
business in a single-device path.

Adding a kernel family = declare a ``CONTRACT`` dict in its module,
add a fixture entry in :func:`build_registry`.  The checker CLI
(``python -m repro.analysis.check``) and the test suite both consume
this registry, so the contract lives in exactly one place.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

from repro.analysis import jaxpr_check

#: The kernel families the registry must cover (checked by the CLI's
#: ``--all`` run and the clean-pass test).
FAMILIES = ("rrr_expand", "greedy_pick", "lazy_greedy", "bucket_insert",
            "cascade", "service")


@dataclasses.dataclass(frozen=True)
class ShapePattern:
    """An intermediate to require or forbid: exact dtype + shape."""
    dtype: str
    shape: Tuple[int, ...]
    note: str = ""

    def describe(self) -> str:
        dims = ",".join(str(d) for d in self.shape)
        tail = f" ({self.note})" if self.note else ""
        return f"{self.dtype}[{dims}]{tail}"


@dataclasses.dataclass(frozen=True)
class KernelContract:
    name: str                     # registry key, e.g. "rrr_expand.resident"
    family: str                   # one of FAMILIES
    description: str
    build: Callable[[], Tuple[Callable, tuple]]   # -> (fn, args) to trace
    expected_launches: int
    expect_in_loop: Optional[bool] = None     # None = don't care
    expected_grid: Optional[Tuple[int, ...]] = None
    forbidden: Tuple[ShapePattern, ...] = ()
    required: Tuple[ShapePattern, ...] = ()
    dtype_whitelist: Optional[frozenset] = None
    max_vmem_bytes: Optional[int] = None      # None = vmem_budget solve
    expected_aliases: Tuple = ()              # input_output_aliases
    check_hlo: bool = True
    forbid_collectives: bool = True
    max_hlo_transposes: Optional[int] = None  # None = unchecked


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    message: str


@dataclasses.dataclass
class ContractReport:
    name: str
    family: str
    violations: list
    stats: dict

    @property
    def ok(self) -> bool:
        return not self.violations

    def as_json(self) -> dict:
        return {
            "name": self.name, "family": self.family, "ok": self.ok,
            "violations": [dataclasses.asdict(v) for v in self.violations],
            "stats": self.stats,
        }


# ------------------------------------------------------------ checking
def run_contract(contract: KernelContract, *,
                 skip_hlo: bool = False) -> ContractReport:
    """Trace the contract's fixture and prove every declared invariant.

    Pure introspection: the fixture is traced (and, for the HLO pass,
    compiled) but never executed.
    """
    import jax
    from repro.kernels import vmem_budget

    fn, args = contract.build()
    jx = jax.make_jaxpr(fn)(*args)
    sites = jaxpr_check.launch_sites(jx)
    violations: list = []

    def bad(rule: str, message: str):
        violations.append(Violation(rule, message))

    # --- launch accounting -------------------------------------------
    if len(sites) != contract.expected_launches:
        bad("launch-count",
            f"expected {contract.expected_launches} pallas_call "
            f"equation(s), found {len(sites)} at "
            f"{[s.path for s in sites]}")
    if contract.expect_in_loop is not None:
        for site in sites:
            if site.in_loop != contract.expect_in_loop:
                where = "inside" if site.in_loop else "outside"
                want = "inside" if contract.expect_in_loop else "outside"
                bad("launch-context",
                    f"launch {site.name!r} sits {where} a loop body at "
                    f"{site.path}; the contract requires it {want} "
                    "(per-iteration vs per-trace accounting)")
    if contract.expected_grid is not None:
        for site in sites:
            if site.grid != contract.expected_grid:
                bad("launch-grid",
                    f"launch {site.name!r} has grid {site.grid}, "
                    f"expected {contract.expected_grid}")

    # --- interpret plumbing ------------------------------------------
    want_interpret = jax.default_backend() != "tpu"
    for site in sites:
        if site.interpret != want_interpret:
            bad("interpret-flag",
                f"launch {site.name!r} traced with "
                f"interpret={site.interpret} on the "
                f"{jax.default_backend()!r} backend (expected "
                f"{want_interpret}) — the interpret= knob is not "
                "plumbed through this entry point")

    # --- donation / aliasing -----------------------------------------
    for site in sites:
        if site.input_output_aliases != tuple(contract.expected_aliases):
            bad("aliasing",
                f"launch {site.name!r} has input_output_aliases="
                f"{site.input_output_aliases}, expected "
                f"{tuple(contract.expected_aliases)}")

    # --- VMEM footprint from block specs -----------------------------
    budget = (contract.max_vmem_bytes
              if contract.max_vmem_bytes is not None
              else vmem_budget.budget_bytes())
    for site in sites:
        if site.vmem_bytes > budget:
            bad("vmem-footprint",
                f"launch {site.name!r} holds {site.vmem_bytes} bytes "
                f"of VMEM-space refs (block specs + scratch), over the "
                f"budget of {budget} bytes")

    # --- layout patterns ---------------------------------------------
    for pattern in contract.forbidden:
        if jaxpr_check.has_intermediate(jx, pattern.dtype, pattern.shape):
            bad("forbidden-intermediate",
                f"forbidden intermediate {pattern.describe()} appears "
                "in the traced program")
    for pattern in contract.required:
        if not jaxpr_check.has_intermediate(jx, pattern.dtype,
                                            pattern.shape):
            bad("missing-intermediate",
                f"required intermediate {pattern.describe()} does not "
                "appear — the contract's forbidden-pattern twin would "
                "be vacuous")

    # --- dtype whitelist ---------------------------------------------
    dtypes = jaxpr_check.dtypes_used(jx)
    if contract.dtype_whitelist is not None:
        extra = dtypes - set(contract.dtype_whitelist)
        if extra:
            bad("dtype-whitelist",
                f"trace touches dtypes {sorted(extra)} outside the "
                f"whitelist {sorted(contract.dtype_whitelist)} (f64 "
                "leak or implicit weak-type upcast)")

    stats = {
        "launches": len(sites),
        "sites": [{
            "name": s.name, "path": list(s.path), "in_loop": s.in_loop,
            "iterations": s.iterations, "grid": list(s.grid),
            "interpret": s.interpret, "vmem_bytes": s.vmem_bytes,
        } for s in sites],
        "dtypes": sorted(dtypes),
        "vmem_budget_bytes": budget,
    }

    # --- HLO pass -----------------------------------------------------
    if contract.check_hlo and not skip_hlo:
        text = jaxpr_check.hlo_text(fn, *args)
        coll = jaxpr_check.collective_stats(text)
        stats["hlo_collectives"] = coll.count
        stats["hlo_transposes"] = jaxpr_check.transpose_count(text)
        if contract.forbid_collectives and coll.count:
            bad("hlo-collective",
                f"single-device path compiles to {coll.count} "
                f"collective(s) moving {coll.total_link_bytes:.0f} "
                f"bytes: {sorted(coll.bytes_by_op)}")
        if (contract.max_hlo_transposes is not None
                and stats["hlo_transposes"] > contract.max_hlo_transposes):
            bad("hlo-transpose",
                f"compiled HLO contains {stats['hlo_transposes']} "
                f"transpose ops, over the contract's bound of "
                f"{contract.max_hlo_transposes}")

    return ContractReport(contract.name, contract.family, violations,
                          stats)


# ------------------------------------------------------------ fixtures
@functools.lru_cache(maxsize=None)
def _sampler_fixture():
    """Canonical sampler graph: small enough to trace fast, but its
    padded forward degree differs from every other width in the trace
    so the gmask forbidden-shape check cannot be vacuous."""
    from repro.graphs import generators
    from repro.graphs.csr import padded_adjacency, padded_forward_adjacency
    g = generators.erdos_renyi(48, 4.0, seed=0)
    nbr, prob, wt = padded_adjacency(g)
    fwd = padded_forward_adjacency(g)
    return g, nbr, prob, wt, fwd


def _sampler_shapes():
    g, nbr, prob, wt, fwd = _sampler_fixture()
    n = g.num_vertices
    df = int(fwd[0].shape[1])
    d_pad = -(-int(nbr.shape[1]) // 32) * 32
    w = 2                                             # theta = 64
    assert df not in (d_pad, 0), (df, d_pad)
    return n, df, w


def _build_sampler(gather: str):
    def build():
        import jax
        from repro.core.rrr import sample_incidence
        g, nbr, prob, wt, fwd = _sampler_fixture()
        n = g.num_vertices
        key = jax.random.key(0)
        return (lambda: sample_incidence(
            nbr, prob, wt, key, theta=64, n=n, model="IC", max_steps=6,
            sampler="kernel", gather=gather, fwd=fwd), ())
    return build


@functools.lru_cache(maxsize=None)
def _rows_fixture():
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, 2 ** 32, (64, 4), dtype=np.uint32))


def _build_maxcover(solver: str):
    def build():
        from repro.core import maxcover
        rows = _rows_fixture()
        return (lambda r: maxcover.greedy_maxcover(r, 8, solver=solver),
                (rows,))
    return build


def _build_maxcover_batch(batch: int):
    def build():
        import jax.numpy as jnp
        from repro.core import maxcover
        rows = _rows_fixture()
        excl = jnp.full((batch, 3), -1, jnp.int32)
        return (lambda r, e: maxcover.greedy_maxcover_batch(
            r, e, 6, solver="resident"), (rows, excl))
    return build


def _build_bucket(kind: str):
    def build():
        import jax.numpy as jnp
        from repro.core import streaming
        state = streaming.init_state(5, 0.077, 10.0, 11)
        if kind == "chunk":
            ids = jnp.zeros((4,), jnp.int32)
            rows = jnp.zeros((4, 11), jnp.uint32)
            return (lambda s, i, r: streaming.insert_chunk(
                s, i, r, k=5, use_kernel=True), (state, ids, rows))
        ids = jnp.zeros((3, 4), jnp.int32)
        rows = jnp.zeros((3, 4, 11), jnp.uint32)
        use_kernel = kind == "stream"
        return (lambda s, i, r: streaming.insert_stream(
            s, i, r, k=5, use_kernel=use_kernel), (state, ids, rows))
    return build


def _build_cascade():
    import numpy as np

    def build():
        import jax
        from repro.core import cascade
        g, _, _, _, _ = _sampler_fixture()
        seeds = np.array([0, 1])
        return (lambda k: cascade.simulate_cascades(
            g, seeds, k, model="IC", num_sims=32, max_steps=4,
            engine="kernel"), (jax.random.key(0),))
    return build


# ------------------------------------------------------------ registry
def _declared(module_contract: dict, key: Optional[str] = None) -> dict:
    """Pull one family's declaration dict (kernel modules with two
    variants nest them under ``variants``)."""
    decl = dict(module_contract)
    variants = decl.pop("variants", None)
    if key is not None:
        decl.update(variants[key])
    return decl


def build_registry() -> Tuple[KernelContract, ...]:
    """Every registered contract — all six kernel families plus the
    zero-launch reference paths that pin the fallbacks."""
    from repro.core import cascade as cascade_mod
    from repro.core import service as service_mod
    from repro.kernels import bucket_insert as bucket_mod
    from repro.kernels import greedy_pick as greedy_mod
    from repro.kernels import lazy_greedy as lazy_mod
    from repro.kernels import rrr_expand as rrr_mod

    n, df, w = _sampler_shapes()
    gmask = ShapePattern("uint32", (n, df, w),
                         "the XLA-side gmask gather's HBM round-trip")

    def wl(decl):
        return frozenset(decl["dtypes"])

    rrr = _declared(rrr_mod.CONTRACT)
    greedy = _declared(greedy_mod.CONTRACT)
    lazy = _declared(lazy_mod.CONTRACT)
    chunk = _declared(bucket_mod.CONTRACT, "chunk")
    stream = _declared(bucket_mod.CONTRACT, "stream")
    casc = _declared(cascade_mod.CONTRACT)
    serve = _declared(service_mod.CONTRACT)

    return (
        KernelContract(
            name="rrr_expand.resident", family="rrr_expand",
            description="kernel sampler, resident coin-plane: one fused "
                        "launch per BFS step, both gathers in-kernel, "
                        "no gmask HBM round-trip",
            build=_build_sampler("resident"),
            expected_launches=rrr["launches"],
            expect_in_loop=rrr["in_loop"],
            forbidden=(gmask,),
            dtype_whitelist=wl(rrr),
            expected_aliases=rrr["aliases"]),
        KernelContract(
            name="rrr_expand.streamed", family="rrr_expand",
            description="kernel sampler, streamed-gmask fallback: one "
                        "fused launch per BFS step; the gmask exists "
                        "here (keeps the resident twin non-vacuous)",
            build=_build_sampler("streamed"),
            expected_launches=rrr["launches"],
            expect_in_loop=rrr["in_loop"],
            required=(gmask,),
            dtype_whitelist=wl(rrr),
            expected_aliases=rrr["aliases"]),
        KernelContract(
            name="greedy_pick.resident", family="greedy_pick",
            description="resident sender: whole k-pick greedy solve in "
                        "ONE top-level launch",
            build=_build_maxcover("resident"),
            expected_launches=greedy["launches"],
            expect_in_loop=greedy["in_loop"],
            dtype_whitelist=wl(greedy),
            expected_aliases=greedy["aliases"]),
        KernelContract(
            name="greedy_pick.scan_ref", family="greedy_pick",
            description="scan reference path stages zero launches "
                        "(pure lax)",
            build=_build_maxcover("scan"),
            expected_launches=0,
            dtype_whitelist=wl(greedy)),
        KernelContract(
            name="lazy_greedy.resident", family="lazy_greedy",
            description="lazy sender: one launch, stale-bound tile "
                        "skipping inside",
            build=_build_maxcover("lazy"),
            expected_launches=lazy["launches"],
            expect_in_loop=lazy["in_loop"],
            dtype_whitelist=wl(lazy),
            expected_aliases=lazy["aliases"]),
        KernelContract(
            name="bucket_insert.chunk", family="bucket_insert",
            description="fused-chunk receiver: one launch per chunk",
            build=_build_bucket("chunk"),
            expected_launches=chunk["launches"],
            expect_in_loop=chunk["in_loop"],
            dtype_whitelist=wl(chunk),
            expected_aliases=chunk["aliases"]),
        KernelContract(
            name="bucket_insert.stream", family="bucket_insert",
            description="pipelined receiver: ONE launch per whole "
                        "[R, C, W] candidate stream",
            build=_build_bucket("stream"),
            expected_launches=stream["launches"],
            expect_in_loop=stream["in_loop"],
            dtype_whitelist=wl(stream),
            expected_aliases=stream["aliases"]),
        KernelContract(
            name="bucket_insert.scan_ref", family="bucket_insert",
            description="scan fallback stages zero launches",
            build=_build_bucket("scan"),
            expected_launches=0,
            dtype_whitelist=wl(stream)),
        KernelContract(
            name="cascade.kernel", family="cascade",
            description="cascade kernel engine: one fused launch per "
                        "diffusion step (shared rrr_expand kernel)",
            build=_build_cascade(),
            expected_launches=casc["launches"],
            expect_in_loop=casc["in_loop"],
            dtype_whitelist=wl(casc),
            expected_aliases=casc["aliases"]),
        KernelContract(
            name="service.batched", family="service",
            description="batched query solve: B concurrent "
                        "seed-constrained queries in ONE launch with no "
                        "grid (each streamed pool tile sweeps every "
                        "query; no batch grid re-reads the pool)",
            build=_build_maxcover_batch(4),
            expected_launches=serve["launches"],
            expect_in_loop=serve["in_loop"],
            expected_grid=(),
            dtype_whitelist=wl(serve),
            expected_aliases=serve["aliases"]),
    )


def contracts_by_name() -> dict:
    return {c.name: c for c in build_registry()}
