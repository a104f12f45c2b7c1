"""S1 RRR sampling: device time per round of the sampler's operations.

The inlined sampler carries no name of its own in the trace (TPU op
events hold no name stack), so S1 is read as the round program's busy
device time less the time the sender (S3) and receiver (S4) kernels
ran; what else the round runs (the partition's permutation, the
shuffle, the best-of merge) is small beside it and counted here."""
from bench.find import load_module


def read(ctx):
    tr = ctx["trace"]
    rounds = tr.count("round")
    s3 = load_module("layer_metrics", "s3_sender_ms").is_sender
    s4 = load_module("layer_metrics", "s4_receiver_ms").is_receiver
    t = tr.op_seconds(lambda o: True) - tr.op_seconds(
        lambda o: s3(o) or s4(o))
    return 1000.0 * t / rounds if rounds and t > 0 else None
