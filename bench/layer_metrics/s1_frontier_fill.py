"""S1 RRR sampling: how full the frontier steps' fixed-size buffers
were, in %: the (sample, vertex) pairs the window's frontier steps held
(``GreediRISOut.frontier_pairs``) over those steps
(``frontier_steps``) times the pairs one step is sized for
(``greediris.frontier_capacity``, or ``frontier_capacity`` in the
context's counters).  Silent on a program that counts no frontier
pairs or sizes no frontier step."""
from bench import program_record
from bench.find import load_module


def _capacity(ctx):
    given = ctx.get("counters")
    if given is not None:
        return given.get("frontier_capacity") if given else None
    found = program_record._run_cell_locals()
    if found is None:
        return None
    try:
        from repro.core.greediris import frontier_capacity
    except ImportError:
        return None
    state, cell = found["state"], found["cell"]
    return frontier_capacity(n=state.n, theta=state.theta,
                             machines=cell.chips, model=state.model,
                             sample_chunks=state.cfg["sample_chunks"])


def read(ctx):
    share = load_module("layer_metrics", "s1_frontier_step_share")
    c = share.window_sums(ctx, "frontier_pairs", "frontier_steps")
    if not c or not c["frontier_steps"]:
        return None
    cap = _capacity(ctx)
    if not cap:
        return None
    return 100.0 * c["frontier_pairs"] / (c["frontier_steps"] * cap)
