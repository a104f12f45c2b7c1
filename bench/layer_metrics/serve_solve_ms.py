"""Service answer: device time per batch of the batched solve program
(``maxcover._greedy_maxcover_batch``), from the trace."""
PROGRAM = "greedy_maxcover_batch"


def is_solve(op) -> bool:
    return PROGRAM in op.module


def read(ctx):
    tr = ctx["trace"]
    batches = tr.count("answer")
    t = tr.op_seconds(is_solve)
    return 1000.0 * t / batches if batches and t > 0 else None
