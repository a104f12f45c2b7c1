"""S3 sender's share of its roofline: the least time the chip could
take for the sender's work, one read of the round's packed incidence
(n_pad rows of theta/32 uint32 words), at the HBM peak, over the
sender's device time.  Every greedy must read each row once for its
first pick, so no sender can read above 100%."""
from bench.find import load_module


def incidence_bytes(shapes) -> int:
    return shapes["n_pad"] * shapes["words"] * 4


def read(ctx):
    ms = load_module("layer_metrics", "s3_sender_ms").read(ctx)
    if ms is None:
        return None
    least = incidence_bytes(ctx["shapes"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1000.0)
