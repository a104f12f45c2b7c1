"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler refuses what interpret mode accepts
(misaligned DMA slices, scalar stores to VMEM, non-float argmax).

Shapes are the chip smoke test's: sender rows [2^18, 512] uint32 on
one chip, [2^16, 2048] per chip on four (theta 16384 per chip), and
W=32 to guard the lane alignment of narrow pools.  The tuned tile
tables are hidden, so every "auto" tile and chunk is the analytic
choice a TPU without a table makes.  Nothing runs, so these say
nothing about results or time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import maxcover, streaming
from repro.kernels import ops, vmem_budget
from repro.kernels.bucket_insert import (bucket_insert_chunk_pallas,
                                         bucket_insert_stream_pallas)
from repro.kernels.greedy_pick import greedy_maxcover_resident_pallas
from repro.kernels.lazy_greedy import greedy_maxcover_lazy_pallas

K = 100
ROWS = [(2 ** 18, 512), (2 ** 18, 32), (2 ** 16, 2048)]


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_TUNED_DIR", str(tmp_path_factory.mktemp("none")))
        vmem_budget.clear_table_cache()
        yield SingleDeviceSharding(topo.devices[0])
    vmem_budget.clear_table_cache()
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,w", ROWS)
@pytest.mark.parametrize("solver", ["resident", "lazy"])
def test_sender_compiles(one_chip, solver, n, w):
    fn = {"resident": greedy_maxcover_resident_pallas,
          "lazy": greedy_maxcover_lazy_pallas}[solver]
    rows = _sds(one_chip, (n, w), jnp.uint32)
    excl = _sds(one_chip, (6,), jnp.int32)
    compiled = jax.jit(
        lambda r, e: fn(r, K, e, interpret=False)).lower(rows,
                                                         excl).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w", [512, 32])
@pytest.mark.parametrize("solver", ["resident", "lazy"])
def test_batched_service_solve_compiles(one_chip, monkeypatch, solver, w):
    """The service's vmapped solve: 8 seed-constrained queries over one
    shared pool (``maxcover.greedy_maxcover_batch``)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    rows = _sds(one_chip, (2 ** 18, w), jnp.uint32)
    excl = _sds(one_chip, (8, 6), jnp.int32)
    compiled = maxcover._greedy_maxcover_batch.lower(
        rows, excl, k=K, solver=solver).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w,total,chunk", [
    (512, K, None),        # one chip: the solved chunk
    (2048, 4 * K, None),   # four chips: m*k candidates of 4x the words
    (32, K, None),
    (512, K, 100),         # chunks that are not whole (8, 128) tiles
    (32, K, 7),
])
def test_receiver_stream_compiles(one_chip, w, total, chunk):
    """The gather receiver's pipelined stream kernel."""
    b = streaming.num_buckets(K, 0.077)
    c = chunk or vmem_budget.receiver_chunk_size(b, w, K, total=total)
    r = -(-total // c)
    args = (_sds(one_chip, (r, c), jnp.int32),
            _sds(one_chip, (r, c, w), jnp.uint32),
            _sds(one_chip, (b, w), jnp.uint32),
            _sds(one_chip, (b,), jnp.int32),
            _sds(one_chip, (b, K), jnp.int32),
            _sds(one_chip, (b,), jnp.float32))
    compiled = jax.jit(lambda *a: bucket_insert_stream_pallas(
        *a, interpret=False)).lower(*args).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w,c", [(2048, K), (32, 7)])
def test_receiver_chunk_compiles(one_chip, w, c):
    """The pipeline aggregate's per-ring-step chunk kernel (C = k)."""
    b = streaming.num_buckets(K, 0.077)
    args = (_sds(one_chip, (c,), jnp.int32),
            _sds(one_chip, (c, w), jnp.uint32),
            _sds(one_chip, (b, w), jnp.uint32),
            _sds(one_chip, (b,), jnp.int32),
            _sds(one_chip, (b, K), jnp.int32),
            _sds(one_chip, (b,), jnp.float32))
    compiled = jax.jit(lambda *a: bucket_insert_chunk_pallas(
        *a, interpret=False)).lower(*args).compile()
    _assert_kernel(compiled)
