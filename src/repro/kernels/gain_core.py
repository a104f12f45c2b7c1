"""The shared AND-NOT + popcount tile body of every gain kernel.

Every Pallas kernel in this package is, at its core, the same
memory-bound contraction over packed uint32 incidence words:

    gain[...] = sum_lanes popcount(x[..., lane] & ~cover[..., lane])

(coverage.py sweeps it over vertex tiles, bucket.py over bucket
covers, topk_gain.py fuses a blockwise argmax behind it, and
bucket_insert.py / greedy_pick.py run it inside VMEM-resident
streaming loops).  This module holds the one implementation of that
tile body plus the block-geometry helpers the wrappers share, so the
AND-NOT+popcount core is written exactly once and every kernel lowers
to the identical VPU population-count path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Lane (last-axis) granularity of the TPU vector unit for 32-bit words;
# every word-axis block is padded up to a multiple of this.
LANE = 128
# Sublane granularity: vertex/row blocks are padded up to a multiple.
SUBLANE = 8


def andnot_popcount(x: jnp.ndarray, cover: jnp.ndarray) -> jnp.ndarray:
    """Elementwise popcount(x & ~cover) -> int32, broadcasting.

    The fused AND-NOT + population-count word op — the single compute
    primitive of every gain kernel.
    """
    return jax.lax.population_count(x & ~cover).astype(jnp.int32)


def gain_tile_sum(x: jnp.ndarray, cover: jnp.ndarray) -> jnp.ndarray:
    """Lane-axis gain reduction of one tile, keepdims.

    x     uint32 [..., bw] incidence words
    cover uint32 [..., bw] running cover (broadcast against x)
    ->    int32  [..., 1]  partial marginal gains

    Callers accumulate this across word tiles; the keepdims shape is
    the [rows, 1] accumulator layout all kernels share.
    """
    return uncovered_tile_sum(x, ~cover)


def uncovered_tile_sum(x: jnp.ndarray, uncovered: jnp.ndarray) -> jnp.ndarray:
    """``gain_tile_sum`` given the cover's complement ``~cover``, for a
    caller that sweeps many tiles against one cover and hoists the
    complement out of its loop."""
    return jnp.sum(jax.lax.population_count(x & uncovered).astype(jnp.int32),
                   axis=-1, keepdims=True)


def effective_block(size: int, block: int, floor: int) -> int:
    """Clamp a requested block edge to the problem size, at least
    ``floor`` (the hardware tile minimum along that axis)."""
    return min(block, max(floor, size))


def padded_size(size: int, block: int) -> int:
    """``size`` rounded up to a whole number of ``block``-sized tiles."""
    return size + ((-size) % block)
