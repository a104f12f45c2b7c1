"""Service answer's share of its roofline: one read of the shared R1
pool (n rows of theta/32 uint32 words) at the HBM peak, over the
batched solve's device time per batch.  Every answer must read the
whole pool for its first pick, so no solve can read above 100%."""
from bench.find import load_module


def pool_bytes(shapes) -> int:
    return shapes["n_pad"] * shapes["words"] * 4


def read(ctx):
    ms = load_module("layer_metrics", "serve_solve_ms").read(ctx)
    if ms is None:
        return None
    least = pool_bytes(ctx["shapes"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1000.0)
