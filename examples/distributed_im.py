"""Distributed GreediRIS on a multi-device mesh (SPMD shard_map).

Re-executes itself with 8 fake host devices (the CPU stand-in for a
TPU pod slice) and runs the full distributed round — sampling shards,
all-to-all shuffle, per-machine greedy, streaming aggregation — for
both aggregation schedules and the Ripples baseline.

    PYTHONPATH=src python examples/distributed_im.py
"""
import os
import subprocess
import sys

if os.environ.get("_IM_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # fake devices: never the chip
    env["_IM_CHILD"] = "1"
    raise SystemExit(subprocess.run([sys.executable] + sys.argv,
                                    env=env).returncode)

import time

import jax
import numpy as np

from repro.core import greediris
from repro.core.diffusion import influence
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency

g = generators.erdos_renyi(2000, 8.0, seed=1)
nbr, prob, wt = padded_adjacency(g)
key = jax.random.key(0)
from repro.launch.mesh import make_im_mesh
mesh = make_im_mesh(8)
print(f"mesh: {mesh.shape} | graph n={g.num_vertices} m={g.num_edges}")

for label, builder in (
    ("greediris/gather", lambda: greediris.build_round(
        mesh, ("machines",), n=g.num_vertices, theta=2048, k=16,
        max_degree=g.max_in_degree(), aggregate="gather")[0]),
    ("greediris/pipeline", lambda: greediris.build_round(
        mesh, ("machines",), n=g.num_vertices, theta=2048, k=16,
        max_degree=g.max_in_degree(), aggregate="pipeline")[0]),
    ("greediris-trunc a=1/8", lambda: greediris.build_round(
        mesh, ("machines",), n=g.num_vertices, theta=2048, k=16,
        max_degree=g.max_in_degree(), alpha_trunc=0.125)[0]),
):
    fn = jax.jit(builder())
    out = jax.block_until_ready(fn(nbr, prob, wt, key))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(nbr, prob, wt, key))
    dt = time.perf_counter() - t0
    # influence() drops the -1 pads in out.seeds itself
    inf = float(influence(g, np.asarray(out.seeds),
                          jax.random.fold_in(key, 9), num_sims=24))
    print(f"{label:24s} coverage={int(out.coverage):5d} "
          f"influence={inf:7.1f} round_time={dt*1e3:7.1f} ms")

fn, _ = greediris.build_ripples_round(mesh, ("machines",),
                                      n=g.num_vertices, theta=2048, k=16)
jfn = jax.jit(fn)
s, c = jax.block_until_ready(jfn(nbr, prob, wt, key))
t0 = time.perf_counter()
s, c = jax.block_until_ready(jfn(nbr, prob, wt, key))
dt = time.perf_counter() - t0
inf = float(influence(g, np.asarray(s), jax.random.fold_in(key, 9),
                      num_sims=24))
print(f"{'ripples-baseline':24s} coverage={int(c):5d} "
      f"influence={inf:7.1f} round_time={dt*1e3:7.1f} ms "
      f"(k global reductions)")
