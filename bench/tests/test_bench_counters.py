"""The round's counters against the plain reference: BFS steps and
(sample, vertex) pairs as the reference walks them, and the lazy
sender's own count of the tiles it swept."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench_tiny  # noqa: F401
from bench import gen
from bench.reference import cover, graph as rg, prng
from bench.reference import round as rround

N = 512
SEED = 2 ** 31 + 99


def jkey(kd):
    return jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32))


@pytest.fixture(scope="module")
def tiny():
    from repro.graphs.csr import (from_edge_list, padded_adjacency,
                                  padded_forward_adjacency)
    spec = {"generator": "gnm_undirected", "n": N, "edges": 4 * N,
            "structure_seed": 0}
    src, dst = gen.edge_list(spec)
    g = from_edge_list(src, dst, N, seed=SEED)
    return dict(g=g, adj=padded_adjacency(g),
                fwd=padded_forward_adjacency(g),
                tab=rg.tables(src, dst, N, SEED))


@pytest.mark.parametrize("model", ["IC", "LT"])
def test_round_counters_match_reference(tiny, model):
    """The counters of one round on one machine, over the round's rows
    in partition order for the sender's count; the units are those of
    ``round_units`` with the same arguments."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core import bitset, greediris
    from repro.kernels import ops as kops
    from repro.kernels.lazy_greedy import num_row_tiles
    from repro.launch.mesh import make_im_mesh
    mesh = make_im_mesh(1)
    d = tiny["g"].max_in_degree()
    fn, _, theta = greediris.build_round(
        mesh, ("machines",), n=N, theta=256, k=8, max_degree=d, model=model,
        aggregate="gather", sampler="packed", fwd=tiny["fwd"],
        sample_chunks=2, solver="lazy", max_steps=32)
    rep = NamedSharding(mesh, P())
    out = jax.jit(fn)(*[jax.device_put(a, rep) for a in tiny["adj"]],
                      jkey(prng.key_from_seed(1000)))
    tab = tiny["tab"]
    kd = prng.key_from_seed(1000)
    samples, verts, steps = rround.sample_round(
        tab, kd, theta=theta, chunks=2, model=model, max_steps=32,
        cumw=rg.lt_thresholds(tab.wt))
    assert int(out.bfs_steps) == sum(steps)
    assert int(out.rrr_pairs) == samples.size
    perm = prng.permutation(prng.fold_in(kd, 0x9E37), N)
    inv = np.empty(N, np.int64)
    inv[perm] = np.arange(N)
    inc = cover.Incidence(inv[verts], samples, N, theta)
    rows = np.stack([bitset.pack_indices(inc.row(r), theta)
                     for r in range(N)])
    swept = kops.greedy_maxcover_lazy(jnp.asarray(rows), 8)[4]
    assert int(out.sender_tiles_swept) == int(swept)
    assert fn.units == greediris.RoundUnits(
        coins_per_bfs_step=128 * N * d if model == "IC" else 128 * N,
        sender_picks=8, sender_tiles_per_pick=num_row_tiles(N))
    assert fn.units == greediris.round_units(
        n=N, theta=256, k=8, max_degree=d, machines=1, model=model,
        sample_chunks=2)
