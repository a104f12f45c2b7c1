"""S1 RRR sampling: uniforms drawn per (sample, vertex) pair sampled,
the window's BFS steps (``GreediRISOut.bfs_steps``) times the coins
one step draws (``RoundUnits.coins_per_bfs_step``) over the pairs
(``GreediRISOut.rrr_pairs``): 1 where every coin drawn lands in a
set."""
from bench import program_record


def read(ctx):
    c = program_record.round_counters(ctx)
    if not c or not c["rrr_pairs"]:
        return None
    return c["bfs_steps"] * c["coins_per_bfs_step"] / c["rrr_pairs"]
