"""S1 RRR sampling: uniforms drawn per (sample, vertex) pair sampled,
the window's ``GreediRISOut.coins_drawn`` over its ``rrr_pairs``.
``s1_coins_per_pair`` counts the dense plane whatever was drawn; this
counts the coins the sampler computed: the slots of each frontier pair
on a frontier step, the whole plane on a dense one."""
from bench.find import load_module


def read(ctx):
    share = load_module("layer_metrics", "s1_frontier_step_share")
    c = share.window_sums(ctx, "coins_drawn", "rrr_pairs")
    if not c or not c["rrr_pairs"]:
        return None
    return c["coins_drawn"] / c["rrr_pairs"]
