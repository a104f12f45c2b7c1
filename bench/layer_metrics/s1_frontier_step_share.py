"""S1 RRR sampling: the share of the window's BFS steps that drew coins
for the frontier's pairs alone (``GreediRISOut.frontier_steps`` over
``bfs_steps``), in %; the rest took the dense step."""
from bench import program_record


def window_sums(ctx, *fields):
    """The window's sums of these ``GreediRISOut`` fields: from
    ``ctx["counters"]`` where the harness passes them, else off the
    outputs in the driver state of the harness's ``run_cell``.  None
    where the program's outputs lack a field."""
    given = ctx.get("counters")
    if given is not None:
        if given and all(f in given for f in fields):
            return {f: given[f] for f in fields}
        return None
    found = program_record._run_cell_locals()
    if found is None:
        return None
    outs = [o for _, o in getattr(found["state"], "outputs", ())]
    if not outs or not all(hasattr(o, f) for o in outs for f in fields):
        return None
    return {f: sum(float(getattr(o, f)) for o in outs) for f in fields}


def read(ctx):
    c = window_sums(ctx, "frontier_steps", "bfs_steps")
    if not c or not c["bfs_steps"]:
        return None
    return 100.0 * c["frontier_steps"] / c["bfs_steps"]
