"""Service answer: the pool reads of the window's batched solves as a
share of one pool stream per query and pick, in percent: the row tiles
the solves read from the pool (``Solve.tile_dmas``, the kernel's own
count) over queries x ``k_max`` x row tiles per pick, summed over the
solves the program recorded in ``service.SOLVES`` inside the window.
100 where each query streams the pool itself; 100 / B where a batch of
B shares one stream.  None where the program keeps no such record (a
program older than it)."""


def _solves():
    try:
        from repro.core import service
    except ImportError:
        return None
    return getattr(service, "SOLVES", None)


def read(ctx):
    record = _solves()
    if record is None:
        return None
    w = ctx["window"]
    rec = list(record)
    if len(rec) == record.maxlen and rec[0].at > w.t0:
        raise RuntimeError("the program's solve record no longer holds "
                           "the start of the window")
    inside = [s for s in rec if w.t0 <= s.at <= w.t1]
    full = sum(s.queries * s.k_max * s.row_tiles for s in inside)
    if not full:
        return None
    return 100.0 * sum(int(s.tile_dmas) for s in inside) / full
