"""Communication-optimized GreediRIS: measured round times.

dense bitmatrix shuffle vs sparse COO shuffle vs Ripples baseline, on
8 SPMD devices (CPU stand-in; the collective-byte deltas at production
scale are in the dry-run/hillclimb records — this bench demonstrates
the same ordering holds for measured wall-clock end to end).
"""
from __future__ import annotations

from benchmarks.common import emit, run_devices

_CODE = """
import json, time
import jax, numpy as np
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency
from repro.core import greediris

g = generators.erdos_renyi(2000, 6.0, seed=1)
nbr, prob, wt = padded_adjacency(g)
key = jax.random.key(0)
from repro.launch.mesh import make_im_mesh
mesh = make_im_mesh(8)
res = {}
for name, kw in (
    ("dense-gather", dict(shuffle="dense")),
    ("dense-pipeline", dict(shuffle="dense", aggregate="pipeline")),
    ("sparse-gather", dict(shuffle="sparse", est_rrr_len=48.0)),
    ("sparse-trunc", dict(shuffle="sparse", est_rrr_len=48.0,
                          alpha_trunc=0.125)),
):
    fn, _, _ = greediris.build_round(
        mesh, ("machines",), n=g.num_vertices, theta=2048, k=16,
        max_degree=g.max_in_degree(), **kw)
    jfn = jax.jit(fn)
    out = jax.block_until_ready(jfn(nbr, prob, wt, key))
    t0 = time.perf_counter()
    out = jax.block_until_ready(jfn(nbr, prob, wt, key))
    res[name] = dict(time_s=time.perf_counter() - t0,
                     cov=int(out.coverage))
print(json.dumps(res))
"""


def main():
    res = run_devices(_CODE, 8)
    base = res["dense-gather"]["time_s"]
    for name, r in res.items():
        emit(f"comm_opt/{name}", r["time_s"] * 1e6,
             f"speedup_vs_dense={base/r['time_s']:.2f}x cov={r['cov']}")


if __name__ == "__main__":
    main()
