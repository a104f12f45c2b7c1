"""Distributed SPMD tests (subprocesses with 8 fake host devices)."""
import textwrap

from tests.conftest import run_with_devices

_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency
from repro.core import greediris, maxcover
from repro.launch.mesh import make_im_mesh
g = generators.erdos_renyi(200, 8.0, seed=1)
nbr, prob, wt = padded_adjacency(g)
key = jax.random.key(0)
mesh = make_im_mesh(8)
"""


def test_gather_and_pipeline_agree_on_validity():
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        for agg in ("gather", "pipeline"):
            fn, n_pad, theta = greediris.build_round(
                mesh, ("machines",), n=200, theta=512, k=8,
                max_degree=g.max_in_degree(), aggregate=agg)
            o = jax.jit(fn)(nbr, prob, wt, key)
            seeds = np.asarray(o.seeds)
            valid = seeds[seeds >= 0]
            assert len(set(valid.tolist())) == len(valid), "dup seeds"
            assert (valid < 200).all()
            assert int(o.coverage) >= int(o.best_local_coverage)
            assert int(o.coverage) > 0
            print(agg, int(o.coverage))
    """))
    assert "gather" in out and "pipeline" in out


def test_seed_quality_vs_ripples_baseline():
    """GreediRIS coverage should be within 25% of the exact distributed
    greedy (paper reports ~2.7% influence gap at m=512)."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        fn, _, theta = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree())
        o = jax.jit(fn)(nbr, prob, wt, key)
        fb, theta_b = greediris.build_ripples_round(
            mesh, ("machines",), n=200, theta=512, k=8)
        sb, cb = jax.jit(fb)(nbr, prob, wt, key)
        ratio = int(o.coverage) / max(int(cb), 1)
        print("ratio", ratio)
        assert ratio >= 0.75, (int(o.coverage), int(cb))
    """))
    assert "ratio" in out


def test_truncation_reduces_payload_keeps_validity():
    run_with_devices(_PRELUDE + textwrap.dedent("""
        fn, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree(), alpha_trunc=0.25)
        o = jax.jit(fn)(nbr, prob, wt, key)
        assert int(o.coverage) >= int(o.best_local_coverage) > 0
    """))


def test_sampling_reproducible_across_mesh_sizes():
    """Leapfrog analogue: per-shard fold_in keys make the OUTPUT
    distribution insensitive to m; with the same key and m the result
    is bit-identical."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        fn, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree())
        a = jax.jit(fn)(nbr, prob, wt, key)
        b = jax.jit(fn)(nbr, prob, wt, key)
        np.testing.assert_array_equal(np.asarray(a.seeds),
                                      np.asarray(b.seeds))
        print("deterministic", int(a.coverage))
    """))
    assert "deterministic" in out


def test_multi_axis_mesh_round():
    """('pod', 'machines') 2x4 mesh — the multi-pod IM configuration."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import generators
from repro.graphs.csr import padded_adjacency
from repro.core import greediris
from repro.launch.mesh import make_im_mesh
g = generators.erdos_renyi(128, 6.0, seed=2)
nbr, prob, wt = padded_adjacency(g)
mesh = make_im_mesh(8, multi_pod=True)
fn, _, _ = greediris.build_round(
    mesh, ("pod", "machines"), n=128, theta=256, k=4,
    max_degree=g.max_in_degree())
o = jax.jit(fn)(nbr, prob, wt, jax.random.key(0))
assert int(o.coverage) > 0
""")


def test_lm_train_step_on_mesh():
    """Sharded LM train step on a (2, 4) = (data, model) mesh."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import get_config
from repro.models import model as model_lib
from repro.launch import specs as specs_lib
from repro.optim import adamw

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("qwen3-moe-235b-a22b", smoke=True)
opt = adamw.OptConfig(warmup_steps=1, total_steps=4)
bundle = model_lib.build(cfg, opt)
with jax.set_mesh(mesh):
    state, specs = bundle.init_state(jax.random.key(0))
    sps = model_lib.concretize_pspecs(
        bundle.state_pspecs(specs), jax.eval_shape(lambda: state), mesh)
    state = jax.tree.map(
        lambda x, p: jax.device_put(x, NamedSharding(mesh, p)),
        state, sps, is_leaf=lambda x: isinstance(x, P))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 17), 0,
                                          cfg.vocab_size)}
    step = jax.jit(bundle.train_step())
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    print("sharded loss", float(m["loss"]))
""")


def test_sparse_shuffle_matches_dense():
    """Communication-optimized COO shuffle must reproduce the dense
    bitmatrix round exactly (same key => same samples => same cover)."""
    out = run_with_devices(_PRELUDE + """
outs = {}
for shuffle in ("dense", "sparse"):
    fn, _, _ = greediris.build_round(
        mesh, ("machines",), n=200, theta=512, k=8,
        max_degree=g.max_in_degree(), shuffle=shuffle, est_rrr_len=32.0)
    outs[shuffle] = jax.jit(fn)(nbr, prob, wt, key)
assert int(outs["dense"].coverage) == int(outs["sparse"].coverage)
np.testing.assert_array_equal(np.asarray(outs["dense"].seeds),
                              np.asarray(outs["sparse"].seeds))
print("sparse==dense", int(outs["dense"].coverage))
""")
    assert "sparse==dense" in out


def test_receiver_routings_bit_identical_on_mesh():
    """gather schedule: scan, legacy chunked scan, and the pipelined
    kernel (explicit and 'auto' chunk_size) must all produce the same
    seeds bit-for-bit; the kernelized ring schedule stays valid."""
    out = run_with_devices(_PRELUDE + """
ref_seeds = None
for label, kw in [("scan", dict(use_kernel=False)),
                  ("scan-chunked", dict(use_kernel=False, chunk_size=8)),
                  ("pipelined", dict(use_kernel=True, chunk_size=8)),
                  ("pipelined-auto", dict(use_kernel=True,
                                          chunk_size="auto"))]:
    fn, _, _ = greediris.build_round(
        mesh, ("machines",), n=200, theta=512, k=8,
        max_degree=g.max_in_degree(), **kw)
    o = jax.jit(fn)(nbr, prob, wt, key)
    if ref_seeds is None:
        ref_seeds, ref_cov = np.asarray(o.seeds), int(o.coverage)
    else:
        np.testing.assert_array_equal(np.asarray(o.seeds), ref_seeds,
                                      err_msg=label)
        assert int(o.coverage) == ref_cov, label
fn, _, _ = greediris.build_round(
    mesh, ("machines",), n=200, theta=512, k=8,
    max_degree=g.max_in_degree(), aggregate="pipeline", use_kernel=True)
o = jax.jit(fn)(nbr, prob, wt, key)
assert int(o.coverage) > 0
print("routings identical", ref_cov)
""")
    assert "routings identical" in out


def test_sender_solver_quad_bit_identical_on_mesh():
    """S3 solver routing: scan, fused, resident, and lazy senders must
    produce identical seeds through the whole distributed round, and
    the resident and lazy senders must each trace to exactly ONE
    pallas_call for the entire greedy solve (receiver kept on the scan
    path so the jaxpr contains only S3 kernels)."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        ref = None
        for solver in ("scan", "fused", "resident", "lazy"):
            fn, _, _ = greediris.build_round(
                mesh, ("machines",), n=200, theta=512, k=8,
                max_degree=g.max_in_degree(), solver=solver)
            o = jax.jit(fn)(nbr, prob, wt, key)
            if ref is None:
                ref = (np.asarray(o.seeds), int(o.coverage))
            else:
                np.testing.assert_array_equal(np.asarray(o.seeds),
                                              ref[0], err_msg=solver)
                assert int(o.coverage) == ref[1], solver
        from repro.analysis import jaxpr_check
        for solver in ("resident", "lazy"):
            fn, _, _ = greediris.build_round(
                mesh, ("machines",), n=200, theta=512, k=8,
                max_degree=g.max_in_degree(), solver=solver)
            jx = jax.make_jaxpr(fn)(nbr, prob, wt, key)
            count = jaxpr_check.count_pallas_calls(jx)
            assert count == 1, (solver, count)
        print("solver quad identical", ref[1])
    """))
    assert "solver quad identical" in out


def test_sampler_triad_bit_identical_on_mesh():
    """S1 sampler routing: dense, packed, and kernel samplers feed the
    whole distributed round identical packed incidence (same key =>
    identical seeds/coverage), on both shuffle schedules; and
    sampler="kernel" traces exactly one rrr_expand pallas_call (one
    fused launch per BFS step — the while body traces once)."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        from repro.graphs.csr import padded_forward_adjacency
        fwd = padded_forward_adjacency(g)
        for shuffle in ("dense", "sparse"):
            ref = None
            for sampler in ("dense", "packed", "kernel"):
                fn, _, _ = greediris.build_round(
                    mesh, ("machines",), n=200, theta=512, k=8,
                    max_degree=g.max_in_degree(), shuffle=shuffle,
                    sampler=sampler,
                    fwd=(None if sampler == "dense" else fwd))
                o = jax.jit(fn)(nbr, prob, wt, key)
                if ref is None:
                    ref = (np.asarray(o.seeds), int(o.coverage))
                else:
                    np.testing.assert_array_equal(
                        np.asarray(o.seeds), ref[0],
                        err_msg=f"{shuffle}/{sampler}")
                    assert int(o.coverage) == ref[1], (shuffle, sampler)
            print(shuffle, "samplers identical", ref[1])
        from repro.analysis import jaxpr_check
        fn, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree(), sampler="kernel", fwd=fwd)
        jx = jax.make_jaxpr(fn)(nbr, prob, wt, key)
        (site,) = jaxpr_check.launch_sites(jx)
        assert site.in_loop     # one fused launch per BFS step
        print("kernel sampler single launch per step")
    """))
    assert "dense samplers identical" in out
    assert "sparse samplers identical" in out
    assert "single launch per step" in out


def test_round_counters_sum_over_machines():
    """The round's counters are psums over the 8 machines: the same on
    every sampler, shuffle and aggregate; every set holds its root, and
    every chunk of every machine takes a BFS step; a solver with no
    counter of its own reports the full sweep (``RoundUnits``)."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        from repro.graphs.csr import padded_forward_adjacency
        fwd = padded_forward_adjacency(g)
        seen = set()
        for shuffle in ("dense", "sparse"):
            for sampler in ("dense", "packed"):
                for aggregate in ("gather", "pipeline"):
                    fn, _, theta = greediris.build_round(
                        mesh, ("machines",), n=200, theta=512, k=8,
                        max_degree=g.max_in_degree(), shuffle=shuffle,
                        sampler=sampler, aggregate=aggregate,
                        sample_chunks=2, solver="scan",
                        fwd=(None if sampler == "dense" else fwd))
                    o = jax.jit(fn)(nbr, prob, wt, key)
                    u = fn.units
                    assert int(o.sender_tiles_swept) == (
                        u.sender_picks * u.sender_tiles_per_pick)
                    seen.add((int(o.bfs_steps), int(o.rrr_pairs)))
        (steps, pairs), = seen
        assert steps >= 8 * 2 and pairs >= theta, (steps, pairs, theta)
        assert u.sender_picks == 8 * 8
        print("counters agree", steps, pairs)
    """))
    assert "counters agree" in out


def test_gather_receiver_issues_one_stream_call(monkeypatch):
    """Acceptance criterion: under the gather schedule with use_kernel,
    the whole m*kk candidate stream goes through exactly ONE
    insert_stream -> bucket_insert_stream pallas_call at trace time
    (and zero per-chunk bucket_insert_chunk calls)."""
    import jax
    import numpy as np
    from repro.core import greediris
    from repro.graphs import generators
    from repro.graphs.csr import padded_adjacency
    from repro.kernels import ops
    from repro.launch.mesh import make_im_mesh

    calls = {"stream": 0, "chunk": 0}
    real_stream = ops.bucket_insert_stream
    real_chunk = ops.bucket_insert_chunk

    def count_stream(*a, **kw):
        calls["stream"] += 1
        return real_stream(*a, **kw)

    def count_chunk(*a, **kw):
        calls["chunk"] += 1
        return real_chunk(*a, **kw)

    monkeypatch.setattr(ops, "bucket_insert_stream", count_stream)
    monkeypatch.setattr(ops, "bucket_insert_chunk", count_chunk)

    g = generators.erdos_renyi(64, 6.0, seed=3)
    nbr, prob, wt = padded_adjacency(g)
    mesh = make_im_mesh(1)
    # odd sizes -> insert_stream's jit cache cannot have this trace yet
    fn, _, _ = greediris.build_round(
        mesh, ("machines",), n=64, theta=96, k=3,
        max_degree=g.max_in_degree(), use_kernel=True, chunk_size=1)
    out = jax.jit(fn)(nbr, prob, wt, jax.random.key(5))
    assert int(out.coverage) > 0
    assert calls["stream"] == 1, calls
    assert calls["chunk"] == 0, calls
    assert np.asarray(out.seeds).shape == (3,)


def test_ripples_unroll_k_matches_loop():
    out = run_with_devices(_PRELUDE + """
fa, _ = greediris.build_ripples_round(mesh, ("machines",), n=200,
                                      theta=512, k=8)
fb, _ = greediris.build_ripples_round(mesh, ("machines",), n=200,
                                      theta=512, k=8, unroll_k=True)
sa, ca = jax.jit(fa)(nbr, prob, wt, key)
sb, cb = jax.jit(fb)(nbr, prob, wt, key)
assert int(ca) == int(cb)
np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
print("unroll ok", int(ca))
""")
    assert "unroll ok" in out


def test_survivors_mask_on_mesh():
    """Partition-loss tolerance through the SPMD round: an all-alive
    survivors mask is bit-inert, and masking out one machine removes
    exactly its partition's candidates (its vertices contribute no
    seeds) while the round stays valid."""
    out = run_with_devices(_PRELUDE + textwrap.dedent("""
        fn, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree())
        base = jax.jit(fn)(nbr, prob, wt, key)
        fn_all, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree(),
            survivors=tuple(range(8)))
        alive = jax.jit(fn_all)(nbr, prob, wt, key)
        np.testing.assert_array_equal(np.asarray(base.seeds),
                                      np.asarray(alive.seeds))
        assert int(base.coverage) == int(alive.coverage)

        drop = 5
        surv = tuple(j for j in range(8) if j != drop)
        fn_d, _, _ = greediris.build_round(
            mesh, ("machines",), n=200, theta=512, k=8,
            max_degree=g.max_in_degree(), survivors=surv)
        o = jax.jit(fn_d)(nbr, prob, wt, key)
        seeds = np.asarray(o.seeds)
        valid = seeds[seeds >= 0]
        # the dead machine's vertex partition contributes no seeds;
        # build_round assigns machine j the slice j of a keyed vertex
        # permutation, so read the dead partition from that same perm
        n_pad = 200 + (-200) % 8
        per = n_pad // 8
        perm = np.asarray(jax.random.permutation(
            jax.random.fold_in(key, 0x9E37), n_pad))
        dead = {int(v) for v in perm[drop * per:(drop + 1) * per]
                if v < 200}
        assert not (set(valid.tolist()) & dead), (valid, drop)
        assert len(set(valid.tolist())) == len(valid)
        assert int(o.coverage) > 0
        assert int(o.coverage) <= int(base.coverage)
        print("base", int(base.coverage), "dropped", int(o.coverage),
              "OK")
    """))
    assert "OK" in out
