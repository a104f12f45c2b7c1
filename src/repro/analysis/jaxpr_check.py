"""Structural jaxpr / HLO introspection for the kernel contract checker.

Every launch/memory/layout invariant this repo cares about used to be
asserted by ``str(jaxpr).count("pallas_call")`` string greps scattered
across the test files.  String matching is fragile — a primitive name
embedded in a shape annotation, a kernel ``name_and_src_info`` string,
or a doc comment inside the printed jaxpr can false-match — and it
cannot see *where* a launch sits (inside a while body = one launch per
BFS step; top level = one launch per solve) or what the launch's block
specs imply for VMEM.  This module walks the ``ClosedJaxpr`` equation
graph instead:

  * :func:`launch_sites` finds every ``pallas_call`` equation,
    recursing into ``scan``/``while``/``cond``/``pjit`` sub-jaxprs,
    and reports for each launch its context path, per-iteration vs
    per-trace accounting (``iterations`` multiplies enclosing scan
    lengths; ``None`` under a while loop whose trip count is dynamic),
    grid, ``interpret`` flag, input/output aliasing, and the static
    VMEM footprint summed from the kernel's block specs (every kernel
    operand/output/scratch ref whose memory space is VMEM).
  * :func:`intermediate_avals` / :func:`has_intermediate` expose the
    XLA-side intermediates so contracts can forbid known HBM
    round-trip shapes (e.g. the resident sampler's ``[n, d_out, W]``
    gmask) structurally instead of by shape-string grep.
  * :func:`dtypes_used` collects every dtype the trace touches
    (including inside kernel bodies, excluding DMA semaphores) for
    whitelist checks — no f64, no implicit weak-type upcasts.
  * :func:`hlo_text` + :func:`collective_stats` /
    :func:`transpose_count` compile an entry point and reuse
    ``repro.distributed.hlo_analysis`` to flag unexpected collectives
    (and optionally transposes) in single-device paths.

Everything here is read-only introspection on traced programs — no
kernel is executed.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Iterator, Optional, Sequence, Tuple

PALLAS_PRIMITIVE = "pallas_call"

#: Context-path components that mean "the launch re-runs every loop
#: iteration at runtime" (the body of a while/scan traces once but
#: executes per iteration).
_LOOP_PARAMS = ("body_jaxpr", "cond_jaxpr")


def as_jaxpr(jx):
    """Unwrap ``ClosedJaxpr`` / ``jax.make_jaxpr`` output to a Jaxpr."""
    inner = getattr(jx, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(jx, "eqns"):
        return jx
    raise TypeError(
        f"expected a Jaxpr or ClosedJaxpr (e.g. from jax.make_jaxpr), "
        f"got {type(jx).__name__} — the checker walks equations "
        "structurally and never accepts pre-stringified jaxprs")


def _param_jaxprs(value, tag: str = ""):
    """Yield ``(tag, Jaxpr)`` for every sub-jaxpr inside an eqn param
    (handles ClosedJaxpr, raw Jaxpr, and tuples/lists of either —
    ``cond`` branches, custom-call jaxprs, ...)."""
    inner = getattr(value, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        yield tag, inner
    elif hasattr(value, "eqns"):
        yield tag, value
    elif isinstance(value, (tuple, list)):
        for i, item in enumerate(value):
            yield from _param_jaxprs(item, f"{tag}[{i}]")


def sub_jaxprs(eqn) -> Iterator[Tuple[str, object]]:
    """``(param_name, Jaxpr)`` pairs for every sub-jaxpr of ``eqn``."""
    for key, value in eqn.params.items():
        yield from _param_jaxprs(value, key)


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus where it sits in the traced program."""
    eqn: object
    path: Tuple[str, ...]        # e.g. ("pjit/jaxpr", "while/body_jaxpr")
    in_loop: bool                # under any while/scan body
    iterations: Optional[int]    # product of enclosing scan lengths;
    #                              None when a while loop (dynamic trip
    #                              count) encloses the site


def iter_eqns(jx, *, into_pallas: bool = False) -> Iterator[EqnSite]:
    """Depth-first walk of every equation, recursing into sub-jaxprs.

    ``pallas_call`` kernel bodies are skipped unless ``into_pallas`` —
    launch counting and intermediate scans are about the XLA-side
    program; kernel-internal refs are covered by the per-launch VMEM
    footprint instead.
    """
    def walk(jaxpr, path, in_loop, iterations):
        for eqn in jaxpr.eqns:
            yield EqnSite(eqn, path, in_loop, iterations)
            if eqn.primitive.name == PALLAS_PRIMITIVE and not into_pallas:
                continue
            prim = eqn.primitive.name
            for key, sub in sub_jaxprs(eqn):
                looped = in_loop
                iters = iterations
                if prim == "while" and key.split("[")[0] in _LOOP_PARAMS:
                    looped, iters = True, None
                elif prim == "scan":
                    looped = True
                    length = eqn.params.get("length")
                    if iters is not None:
                        iters = (iters * int(length)
                                 if length is not None else None)
                yield from walk(sub, path + (f"{prim}/{key}",),
                                looped, iters)

    yield from walk(as_jaxpr(jx), (), False, 1)


# ------------------------------------------------------------ launches
@dataclasses.dataclass(frozen=True)
class LaunchSite:
    """One ``pallas_call`` equation, structurally decoded."""
    name: str                         # kernel name (debug info)
    path: Tuple[str, ...]
    in_loop: bool
    iterations: Optional[int]         # per-trace multiplier (see EqnSite)
    grid: Tuple[int, ...]
    interpret: bool
    input_output_aliases: Tuple
    vmem_bytes: int                   # static footprint from block specs
    vmem_by_space: dict               # bytes per memory space (vmem/any/..)


def _ref_bytes(aval) -> int:
    inner = getattr(aval, "inner_aval", aval)
    shape = getattr(inner, "shape", None)
    dtype = getattr(inner, "dtype", None)
    itemsize = getattr(dtype, "itemsize", None)
    if shape is None or itemsize is None:   # e.g. DMA semaphores
        return 0
    return math.prod(shape) * itemsize


def launch_vmem_bytes(eqn) -> Tuple[int, dict]:
    """Static memory footprint of one launch, from its block specs.

    Sums the kernel jaxpr's operand/output/scratch refs by memory
    space.  Refs whose space is VMEM (or unannotated, which lowers to
    VMEM) count toward the budgeted footprint; ``any`` (HBM-resident
    streams) and DMA semaphores do not.
    """
    by_space: dict = {}
    for var in eqn.params["jaxpr"].invars:
        aval = getattr(var, "aval", None)
        space = str(getattr(aval, "memory_space", None))
        by_space[space] = by_space.get(space, 0) + _ref_bytes(aval)
    vmem = by_space.get("vmem", 0) + by_space.get("None", 0)
    return vmem, by_space


def launch_sites(jx) -> list[LaunchSite]:
    """Every ``pallas_call`` in the traced program, structurally."""
    sites = []
    for site in iter_eqns(jx):
        if site.eqn.primitive.name != PALLAS_PRIMITIVE:
            continue
        eqn = site.eqn
        info = eqn.params.get("name_and_src_info")
        name = (eqn.params.get("name")
                or getattr(info, "name", PALLAS_PRIMITIVE))
        grid_mapping = eqn.params.get("grid_mapping")
        vmem, by_space = launch_vmem_bytes(eqn)
        sites.append(LaunchSite(
            name=name,
            path=site.path,
            in_loop=site.in_loop,
            iterations=site.iterations,
            grid=tuple(getattr(grid_mapping, "grid", ()) or ()),
            interpret=bool(eqn.params.get("interpret", False)),
            input_output_aliases=tuple(
                eqn.params.get("input_output_aliases", ()) or ()),
            vmem_bytes=vmem,
            vmem_by_space=by_space,
        ))
    return sites


def count_pallas_calls(jx) -> int:
    """Structural replacement for ``str(jaxpr).count("pallas_call")``:
    the number of ``pallas_call`` *equations* in the traced program
    (each loop body counts once — it traces once)."""
    return len(launch_sites(jx))


# ------------------------------------------------------- intermediates
def intermediate_avals(jx) -> Iterator[Tuple[object, Tuple[str, ...]]]:
    """``(aval, path)`` of every equation output in the XLA-side
    program (kernel bodies excluded — see :func:`iter_eqns`)."""
    for site in iter_eqns(jx):
        for var in site.eqn.outvars:
            aval = getattr(var, "aval", None)
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                yield aval, site.path


def has_intermediate(jx, dtype: str, shape: Sequence[int]) -> bool:
    """True iff any XLA-side intermediate has exactly this dtype and
    shape — the structural version of grepping the printed jaxpr for
    ``u32[n,d,w]`` (which can false-match annotation text)."""
    want = tuple(shape)
    return any(
        tuple(aval.shape) == want and str(aval.dtype) == dtype
        for aval, _ in intermediate_avals(jx))


# -------------------------------------------------------------- dtypes
def dtypes_used(jx) -> set[str]:
    """Every dtype the trace touches, kernel bodies included.

    DMA-semaphore refs are excluded — they are synchronization
    hardware state (int16 on this backend), not data the contract's
    whitelist is about.
    """
    seen: set[str] = set()

    def visit_var(var):
        aval = getattr(var, "aval", None)
        if str(getattr(aval, "memory_space", None)) == "semaphore_mem":
            return
        inner = getattr(aval, "inner_aval", aval)
        dtype = getattr(inner, "dtype", None)
        if dtype is not None:
            seen.add(str(dtype))

    def visit(jaxpr):
        for var in (*jaxpr.invars, *jaxpr.outvars, *jaxpr.constvars):
            visit_var(var)
        for eqn in jaxpr.eqns:
            for var in (*eqn.invars, *eqn.outvars):
                visit_var(var)
            for _, sub in sub_jaxprs(eqn):
                visit(sub)

    visit(as_jaxpr(jx))
    return seen


# ----------------------------------------------------------------- HLO
def hlo_text(fn, *args) -> str:
    """Post-optimization HLO of ``jit(fn)(*args)`` on the active
    backend (compiles, does not execute)."""
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def collective_stats(text: str):
    """Collective accounting of compiled HLO — the exact parser the
    distributed roofline uses (``repro.distributed.hlo_analysis``), so
    the contract checker and the dry-run cost model can never disagree
    about what counts as a collective."""
    from repro.distributed import hlo_analysis
    return hlo_analysis.parse_collectives(text)


_TRANSPOSE_RE = re.compile(r"^\s*(?:%\S+\s*=\s*)?\S+\s+transpose\(",
                           re.MULTILINE)


def transpose_count(text: str) -> int:
    """Number of ``transpose`` ops in compiled HLO (layout churn the
    single-device contracts can bound)."""
    return len(_TRANSPOSE_RE.findall(text))
