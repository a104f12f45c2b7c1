"""S3 sender: device time per round of the sender kernels (the lazy
and resident greedy ``pallas_call``s), from the trace."""
SENDERS = ("lazy_greedy", "greedy_pick_resident")


def is_sender(op) -> bool:
    return any(s in op.name for s in SENDERS)


def read(ctx):
    tr = ctx["trace"]
    rounds = tr.count("round")
    t = tr.op_seconds(is_sender)
    return 1000.0 * t / rounds if rounds and t > 0 else None
