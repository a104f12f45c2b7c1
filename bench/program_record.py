"""What the program records of its own work, for the per-layer readers.

The trace reduction keeps the benchmark's own spans only, and the
readers get no driver state, so the program's records are found here;
each function returns None where the program keeps no such record (a
program older than its spans and counters):

- The program's spans (``repro.runtime.spans``): every span leaves its
  interval on ``time.perf_counter``'s clock in ``spans.RECORD``, the
  clock the drivers time the window on, and is read inside the window
  (``window.t0`` to ``window.t1``).
- The round's counters (``GreediRISOut.bfs_steps``, ``rrr_pairs``,
  ``sender_tiles_swept``), summed over the window's rounds.  The
  outputs are read after the window, from the driver state that the
  harness's ``run_cell`` holds while it calls the readers, unless the
  context already carries ``counters``; the denominators are the
  program's own (``greediris.round_units``).
"""
from __future__ import annotations

import sys

COUNTERS = ("bfs_steps", "rrr_pairs", "sender_tiles_swept")


def _spans():
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def span_ms_per_batch(ctx, name: str):
    """Host milliseconds per batch of the window inside the program's
    spans ``name``.  A name the program does not emit is an error, so a
    renamed span cannot read as nothing."""
    spans = _spans()
    if spans is None:
        return None
    if name not in spans.SPANS:
        raise KeyError(f"the program emits no span {name!r}; it emits "
                       f"{spans.SPANS}")
    w = ctx["window"]
    rec = list(spans.RECORD)
    if len(rec) == spans.RECORD.maxlen and rec[0][1] > w.t0:
        raise RuntimeError("the program's span record no longer holds "
                           "the start of the window")
    t = sum(end - start for n, start, end in rec
            if n == name and start >= w.t0 and end <= w.t1)
    return 1000.0 * t / len(w.items) if w.items and t > 0 else None


def _run_cell_locals():
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "run_cell" and "state" in f.f_locals:
            return f.f_locals
        f = f.f_back
    return None


def round_counters(ctx):
    """The window's round counters, summed, with ``rounds`` and the
    fields of the program's ``RoundUnits``."""
    if ctx.get("counters") is not None:
        return ctx["counters"] or None
    found = _run_cell_locals()
    if found is None:
        return None
    state, cell = found["state"], found["cell"]
    outs = [o for _, o in getattr(state, "outputs", ())]
    if not outs or not all(hasattr(outs[0], c) for c in COUNTERS):
        return None
    from repro.core.greediris import round_units
    cfg = state.cfg
    units = round_units(n=state.n, theta=state.theta, k=state.k,
                        max_degree=state.d_max, machines=cell.chips,
                        model=state.model,
                        sample_chunks=cfg["sample_chunks"],
                        coin_chunk=cfg["coin_chunk"])
    return dict({c: sum(int(getattr(o, c)) for o in outs)
                 for c in COUNTERS}, rounds=len(outs), **units._asdict())
