"""S4 receiver: device time per round of the bucket-insert kernels,
from the trace."""
RECEIVERS = ("bucket_insert_stream", "bucket_insert_chunk")


def is_receiver(op) -> bool:
    return any(s in op.name for s in RECEIVERS)


def read(ctx):
    tr = ctx["trace"]
    rounds = tr.count("round")
    t = tr.op_seconds(is_receiver)
    return 1000.0 * t / rounds if rounds and t > 0 else None
