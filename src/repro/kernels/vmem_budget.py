"""Shared VMEM-budget accounting for every Pallas kernel family.

One module owns the per-core VMEM budget, the analytic tile/chunk
solves that size kernel scratch against it, and the autotuned
per-backend tile tables — so the autotuner (``benchmarks/autotune.py``)
and the resolve-time "auto" policies consult the exact same model.
Before this module each kernel carried its own copy of the arithmetic
(the receiver's ``auto_chunk_size`` in ``bucket_insert.py``, hand-held
block constants elsewhere), which is how the sampler's heavy-hub
overflow went unmodeled.

Resolution order for every "auto" knob:

  1. the tuned table for the active backend
     (``benchmarks/tuned/<backend>.json``, written by
     ``python -m benchmarks.autotune``; ``REPRO_TUNED_DIR`` overrides
     the directory) — but always clamped by the analytic budget solve,
     so a table tuned on a different workload can never overflow VMEM;
  2. the analytic solve from the VMEM budget model below.

Budget model (all word-sized = 4-byte units):

  receiver  (``bucket_insert_stream``)  state = 2·B·Wp + 2·B·k + 4·B
            words resident; the solved-for term is the [2, C, Wp]
            double-buffered candidate rows.
  sampler   (``rrr_expand``)            state = 4·n_pad·Wp (frontier/
            visited in+out) + BV·Wp (hit scratch) [+ the coin-plane
            rows·Wp when ``gather="resident"``]; the solved-for term
            is the double-buffered forward-slot stream — per slot
            2·BV·(w+1) words streamed (gmask + index) plus one lane of
            flattening pad, or 2·BV·(Wp+2) gather words resident.
  senders   (``greedy_pick`` / ``lazy_greedy``)  the [2, BV, Wp] row
            double buffer; BV=128 is the analytic default and the
            tuned table may override it.  A batched ``greedy_pick``
            launch adds per query (k + 1 + 8)·Wp + 2·k + E words
            (selected rows, cover, winner block, seed/gain and
            exclusion slots); the solved-for term is the queries one
            launch holds.

``vmem_budget_bytes=None`` everywhere means "the default budget",
overridable process-wide via ``REPRO_VMEM_BUDGET_BYTES`` (how the
heavy-hub tests force the tiled path on CI-sized fixtures).  All
solves run at trace time on static shapes; none of the solved knobs
affects results — tile order is bit-exact by construction (OR
accumulation is order-free, argmax carries are strict-greater).
``coin_chunk`` is the one searched knob that is NOT auto-applied: it
is part of the PRNG stream (acts like a seed), so the tuned value is
recorded for explicit opt-in only.
"""
from __future__ import annotations

import functools
import json
import os
from pathlib import Path
from typing import Optional

from repro.kernels import gain_core

# Per-core VMEM the auto policies budget against (v5e ~16 MiB, minus
# headroom for Mosaic's own spills and the scalar blocks).
VMEM_BUDGET_BYTES = 14 * (1 << 20)
WORD_BYTES = 4
DEFAULT_BLOCK_V = 128

#: kernel families the autotuner searches / the tuned tables key on.
FAMILIES = ("rrr_expand", "greedy_pick", "lazy_greedy",
            "bucket_insert_stream")

GATHER_MODES = ("resident", "streamed", "auto")


def budget_bytes(override: Optional[int] = None) -> int:
    """The active VMEM budget: explicit override > env > default."""
    if override is not None:
        return int(override)
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    return int(env) if env else VMEM_BUDGET_BYTES


# ---------------------------------------------------------------- tuned
def tuned_dir() -> Path:
    env = os.environ.get("REPRO_TUNED_DIR")
    if env:
        return Path(env)
    # src/repro/kernels/vmem_budget.py -> repo root / benchmarks / tuned
    return Path(__file__).resolve().parents[3] / "benchmarks" / "tuned"


@functools.lru_cache(maxsize=None)
def _load_table(path_str: str):
    try:
        with open(path_str) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    fams = doc.get("families")
    return fams if isinstance(fams, dict) else None


def clear_table_cache() -> None:
    """Drop the cached tuned tables (tests repoint ``REPRO_TUNED_DIR``)."""
    _load_table.cache_clear()


def tuned_value(family: str, param: str,
                backend: Optional[str] = None) -> Optional[int]:
    """The tuned table entry for ``(family, param)``, or None.

    ``backend=None`` reads the active JAX backend.  Malformed or
    non-positive entries read as absent — the analytic solve then
    applies unclamped.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    table = _load_table(str(tuned_dir() / f"{backend}.json"))
    if not table:
        return None
    entry = table.get(family)
    if not isinstance(entry, dict):
        return None
    try:
        v = int(entry[param])
    except (KeyError, TypeError, ValueError):
        return None
    return v if v >= 1 else None


def auto_block_v(family: str, default: int = DEFAULT_BLOCK_V,
                 backend: Optional[str] = None) -> int:
    """Row-tile size for ``family``: tuned table else ``default``.

    Deliberately shape-independent so helpers that reason about tile
    counts (``lazy_greedy.num_row_tiles``) agree with the kernels.
    block_v never changes results — only scratch shape and launch
    geometry.
    """
    return tuned_value(family, "block_v", backend) or default


# -------------------------------------------------------------- senders
def sender_batch_queries(k: int, excl: int, wp: int, bv: int) -> int:
    """Queries one batched ``greedy_pick`` launch can hold (>= 1): the
    budget less the [2, BV, Wp] row double buffer, over each query's
    resident words."""
    per_query = WORD_BYTES * ((k + 1 + gain_core.SUBLANE) * wp
                              + 2 * k + excl)
    avail = budget_bytes() - WORD_BYTES * 2 * bv * wp
    return int(max(1, avail // per_query))


# ------------------------------------------------------------- receiver
def receiver_chunk_size(num_buckets: int, num_words: int, k: int,
                        total: Optional[int] = None,
                        vmem_budget_bytes: Optional[int] = None,
                        block_w: int = 512,
                        backend: Optional[str] = None) -> int:
    """Solve the pipelined receiver's chunk size C from the VMEM budget
    (the former ``bucket_insert.auto_chunk_size``, now table-aware).

    Resident bytes for a [R, C, W] stream through B buckets of
    capacity k:

      covers in+out   2 * B * Wp          (Wp = W padded to block_w)
      seeds  in+out   2 * B * k
      counts/thr      ~4 * B
      rows double-buf 2 * C * Wp          (the solved-for term)

    Returns the largest C (multiple of 8 sublanes, >= 8) whose double
    buffer fits the remaining budget, clamped to the tuned table's
    ``bucket_insert_stream.chunk_size`` preference when one exists;
    ``total`` (the stream length m*kk) caps C so a short stream is not
    over-chunked.
    """
    bw = gain_core.effective_block(num_words, block_w, gain_core.LANE)
    wp = gain_core.padded_size(num_words, bw)
    state_bytes = WORD_BYTES * (2 * num_buckets * wp
                                + 2 * num_buckets * k
                                + 4 * num_buckets)
    avail = max(0, budget_bytes(vmem_budget_bytes) - state_bytes)
    c = avail // (2 * wp * WORD_BYTES)
    tuned = tuned_value("bucket_insert_stream", "chunk_size", backend)
    if tuned is not None:
        c = min(c, tuned)
    c = max(8, (c // 8) * 8)
    if total is not None and total > 0:
        c = min(c, max(8, -(-total // 8) * 8))
    return int(c)


# -------------------------------------------------------------- sampler
def _sampler_geometry(n: int, w: int, block_v: Optional[int],
                      backend: Optional[str] = None):
    """(bv, n_pad, wp) exactly as the rrr_expand wrappers compute them."""
    bv = (auto_block_v("rrr_expand", backend=backend)
          if block_v is None else block_v)
    bv = gain_core.effective_block(n, bv, gain_core.SUBLANE)
    bv = gain_core.padded_size(bv, gain_core.SUBLANE)
    n_pad = gain_core.padded_size(n, bv)
    wp = gain_core.padded_size(w, gain_core.LANE)
    return bv, n_pad, wp


def sampler_state_bytes(n_pad: int, wp: int, bv: int,
                        plane_rows: int = 0) -> int:
    """Resident words of one expansion step: frontier/visited in+out,
    the [BV, Wp] hit scratch, and (resident gather) the coin plane."""
    return WORD_BYTES * (4 * n_pad * wp + bv * wp + plane_rows * wp)


def sampler_d_tile(df: int, w: int, *, block_v: int, n_pad: int,
                   resident: bool, plane_rows: int = 0,
                   vmem_budget_bytes: Optional[int] = None) -> int:
    """Largest forward-slot chunk per stream tile that keeps the
    expansion kernel under the VMEM budget (>= 1 always — a single
    slot per tile is the best-effort floor on pathological hubs).

    streamed: per slot the double-buffered stream carries 2·BV·w gmask
    words + 2·BV index words, plus at most one LANE of flattening pad
    per buffer.  resident: per slot the in-kernel gathers materialize
    2·BV·Wp words (gathered frontier + gathered plane) and the stream
    carries 2·2·BV index words.
    """
    wp = gain_core.padded_size(w, gain_core.LANE)
    state = sampler_state_bytes(n_pad, wp, block_v, plane_rows)
    avail = budget_bytes(vmem_budget_bytes) - state
    if resident:
        per_slot = (2 * wp + 4) * block_v * WORD_BYTES
        dt = avail // per_slot
    else:
        # 2·BV·(gqd + dt) words with gqd = pad(dt·w, LANE): solve with
        # the lane pad charged up front so the rounded gqd still fits.
        avail -= 2 * block_v * gain_core.LANE * WORD_BYTES
        per_slot = 2 * block_v * (w + 1) * WORD_BYTES
        dt = avail // per_slot
    return int(max(1, min(df, dt)))


def resolve_gather(gather: Optional[str], *, n: int, d_pad: int, w: int,
                   block_v: Optional[int] = None,
                   vmem_budget_bytes: Optional[int] = None,
                   backend: Optional[str] = None) -> str:
    """Resolve the kernel sampler's ``gather=`` knob to a concrete mode.

    "resident" keeps the per-step packed coin-plane
    (uint32 [n·d_pad (+1), W]) VMEM-resident and gathers BOTH halves
    (frontier rows at fwd_nbr, coin words at rev_slot) inside the
    kernel — no XLA-side [n, d_out, W] gmask, no HBM round-trip.
    "streamed" is the fallback gmask-stream layout for graphs whose
    coin-plane exceeds VMEM.  "auto" (and None) picks resident iff the
    plane + packed state + a one-slot gather tile fit the budget.
    """
    if gather is None:
        gather = "auto"
    if gather not in GATHER_MODES:
        raise ValueError(
            f"unknown gather {gather!r}; expected one of {GATHER_MODES} "
            "(the kernel sampler's coin-gather layout — 'resident' "
            "keeps the packed coin-plane in VMEM and gathers in-kernel, "
            "'streamed' streams pre-gathered gmask tiles, 'auto' solves "
            "from the VMEM budget)")
    if gather != "auto":
        return gather
    bv, n_pad, wp = _sampler_geometry(n, w, block_v, backend)
    plane_rows = gain_core.padded_size(n * d_pad + 1, gain_core.SUBLANE)
    state = sampler_state_bytes(n_pad, wp, bv, plane_rows)
    min_tile = (2 * wp + 4) * bv * WORD_BYTES     # one-slot gather tile
    if state + min_tile <= budget_bytes(vmem_budget_bytes):
        return "resident"
    return "streamed"
