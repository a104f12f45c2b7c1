"""S1 RRR sampling: device milliseconds per BFS step, the S1 time per
round (as ``s1_sample_ms`` reads it from the trace) over the BFS steps
a round of the window counted (``GreediRISOut.bfs_steps``)."""
from bench import program_record
from bench.find import load_module


def read(ctx):
    c = program_record.round_counters(ctx)
    if not c or not c["bfs_steps"]:
        return None
    ms = load_module("layer_metrics", "s1_sample_ms").read(ctx)
    if ms is None:
        return None
    return ms * c["rounds"] / c["bfs_steps"]
