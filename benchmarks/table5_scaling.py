"""Paper Table 5 / Figs 3-5: strong scaling of GreediRIS with m.

Fixed problem (n, theta, k); machine count sweeps 1..8 host devices
(one subprocess per mesh size — device count is locked at jax init).
Reports total round time and the seed-selection share, mirroring the
shaded regions of Fig. 5.
"""
from __future__ import annotations

from benchmarks.common import emit, run_devices

_CODE = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency
from repro.core import greediris, maxcover, bitset
from repro.core.rrr import rrr_batch

m = {m}
g = generators.erdos_renyi(2000, 6.0, seed=1)
nbr, prob, wt = padded_adjacency(g)
key = jax.random.key(0)
from repro.launch.mesh import make_im_mesh
mesh = make_im_mesh(m)
fn, _, theta = greediris.build_round(
    mesh, ("machines",), n=g.num_vertices, theta={theta}, k={k},
    max_degree=g.max_in_degree(), model="IC", alpha_trunc={alpha})
jfn = jax.jit(fn)
out = jax.block_until_ready(jfn(nbr, prob, wt, key))
t0 = time.perf_counter()
out = jax.block_until_ready(jfn(nbr, prob, wt, key))
total = time.perf_counter() - t0

# sampling-only time (to split select share like Fig. 4/5)
theta_local = theta // m
@jax.jit
def sample_only(key):
    roots = jax.random.randint(key, (theta_local,), 0, g.num_vertices)
    return rrr_batch(nbr, prob, wt, roots, key, model="IC", max_steps=32)
jax.block_until_ready(sample_only(key))
t0 = time.perf_counter(); jax.block_until_ready(sample_only(key))
t_sample = time.perf_counter() - t0
print(json.dumps(dict(total_s=total, sample_s=t_sample,
                      coverage=int(out.coverage))))
"""


def main():
    for alpha, tag in ((1.0, "greediris"), (0.125, "greediris-trunc")):
        base = None
        for m in (1, 2, 4, 8):
            res = run_devices(_CODE.format(m=m, theta=2048, k=16,
                                           alpha=alpha), m)
            if base is None:
                base = res["total_s"]
            sel_share = max(0.0, 1.0 - res["sample_s"] / res["total_s"])
            emit(f"table5/{tag}/m={m}", res["total_s"] * 1e6,
                 f"speedup={base/res['total_s']:.2f}x "
                 f"select_share={sel_share:.2f} cov={res['coverage']}")


if __name__ == "__main__":
    main()
