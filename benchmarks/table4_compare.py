"""Paper Table 4: GreediRIS vs GreediRIS-trunc vs Ripples(-style) —
runtime and quality on several graph topologies under IC and LT.

Real multi-device execution (8 fake host devices in a subprocess, one
MPI-rank analogue per device).  "Ripples" here is the faithful
k-global-reductions baseline, executed on the same mesh.
"""
from __future__ import annotations


from benchmarks.common import emit, run_devices

_CODE = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency
from repro.core import greediris
from repro.core.diffusion import influence

g = generators.{gen}
nbr, prob, wt = padded_adjacency(g)
key = jax.random.key(0)
from repro.launch.mesh import make_im_mesh
mesh = make_im_mesh(8)
n = g.num_vertices
res = {{}}
for name, kind, alpha in (("greediris", "g", 1.0),
                          ("greediris-trunc", "g", 0.125),
                          ("ripples", "r", 1.0)):
    if kind == "g":
        fn, _, theta = greediris.build_round(
            mesh, ("machines",), n=n, theta={theta}, k={k},
            max_degree=g.max_in_degree(), model="{model}",
            alpha_trunc=alpha)
        jfn = jax.jit(fn)
        out = jax.block_until_ready(jfn(nbr, prob, wt, key))
        t0 = time.perf_counter(); jax.block_until_ready(jfn(nbr, prob, wt, key))
        dt = time.perf_counter() - t0
        seeds = np.asarray(out.seeds); cov = int(out.coverage)
    else:
        fn, theta = greediris.build_ripples_round(
            mesh, ("machines",), n=n, theta={theta}, k={k},
            model="{model}")
        jfn = jax.jit(fn)
        s, c = jax.block_until_ready(jfn(nbr, prob, wt, key))
        t0 = time.perf_counter(); jax.block_until_ready(jfn(nbr, prob, wt, key))
        dt = time.perf_counter() - t0
        seeds = np.asarray(s); cov = int(c)
    seeds = seeds[seeds >= 0]
    inf = float(influence(g, seeds, jax.random.fold_in(key, 7),
                          model="{model}", num_sims=12))
    res[name] = dict(time_s=dt, coverage=cov, influence=inf)
print(json.dumps(res))
"""


def main():
    graphs = {
        "er2k": ("erdos_renyi(2000, 8.0, seed=1)", 2048),
        "er5k": ("erdos_renyi(5000, 6.0, seed=4)", 2048),
        "rmat1k": ("rmat(10, 4096, seed=3)", 1024),
    }
    for gname, (gen, theta) in graphs.items():
        for model in ("IC", "LT"):
            res = run_devices(
                _CODE.format(gen=gen, theta=theta, k=16, model=model), 8)
            base = res["ripples"]
            for name, r in res.items():
                speedup = base["time_s"] / max(r["time_s"], 1e-9)
                dq = 100.0 * (r["influence"] - base["influence"]) / \
                    max(base["influence"], 1e-9)
                emit(f"table4/{gname}/{model}/{name}",
                     r["time_s"] * 1e6,
                     f"speedup_vs_ripples={speedup:.2f}x "
                     f"quality_delta={dq:+.1f}% cov={r['coverage']}")


if __name__ == "__main__":
    main()
