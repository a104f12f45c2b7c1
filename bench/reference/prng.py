"""Threefry-2x32 counter PRNG in NumPy, stream-compatible with JAX.

The reference sampler needs the coin that JAX draws for one
(sample, vertex, slot) out of a draw of shape ``[batch, n, slots]``
without drawing the whole array (tens of GB at the benchmark's
sizes).  JAX's partitionable threefry (the default since JAX 0.5)
makes every element a function of its flat index alone:
``bits[i] = y1 ^ y2`` with ``(y1, y2) = threefry(key, (i >> 32, i &
0xffffffff))``.  Keys are ``(hi, lo)`` pairs of uint32.

Only the standard algorithm is used here; JAX serves as an oracle in
the tests, never as a dependency of the stream.
"""
from __future__ import annotations

import numpy as np

U32 = np.uint32
_MASK = 0xFFFFFFFF
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, r):
    return (x << U32(r)) | (x >> U32(32 - r))


def threefry2x32(key, x1, x2):
    """Threefry-2x32 with 20 rounds over uint32 arrays x1, x2."""
    k1, k2 = U32(key[0]), U32(key[1])
    ks = (k1, k2, k1 ^ k2 ^ U32(0x1BD11BDA))
    x = [np.asarray(x1, U32) + ks[0], np.asarray(x2, U32) + ks[1]]
    with np.errstate(over="ignore"):
        for i in range(5):
            for r in _ROT[i % 2]:
                x[0] = x[0] + x[1]
                x[1] = _rotl(x[1], r) ^ x[0]
            x[0] = x[0] + ks[(i + 1) % 3]
            x[1] = x[1] + ks[(i + 2) % 3] + U32(i + 1)
    return x[0], x[1]


def key_from_seed(seed: int):
    """A 64-bit seed as a key pair, the way ``jax.random.key`` makes
    one from a 64-bit integer."""
    seed = int(seed)
    return ((seed >> 32) & _MASK, seed & _MASK)


def _pair(key, hi, lo):
    with np.errstate(over="ignore"):
        y1, y2 = threefry2x32(key, np.asarray([hi], U32),
                              np.asarray([lo], U32))
    return (int(y1[0]), int(y2[0]))


def fold_in(key, data: int):
    return _pair(key, 0, int(data) & _MASK)


def split(key, num: int = 2):
    return [_pair(key, 0, i) for i in range(num)]


def bits32(key, index):
    """32 random bits at flat indices ``index`` (int array) of a draw."""
    index = np.asarray(index, np.uint64)
    hi = (index >> np.uint64(32)).astype(U32)
    lo = (index & np.uint64(_MASK)).astype(U32)
    y1, y2 = threefry2x32(key, hi, lo)
    return y1 ^ y2


def uniform_f32(bits):
    """``jax.random.uniform`` on [0, 1) in float32 from 32 bits."""
    b = (np.asarray(bits, U32) >> U32(9)) | U32(0x3F800000)
    return b.view(np.float32) - np.float32(1.0)


def uniform_bf16(bits):
    """``jax.random.uniform(..., dtype=bfloat16)`` from the same counter,
    as float32: JAX draws 8 bits for a bfloat16 and keeps 7 of them
    as the mantissa.  The lower-precision coin of the control."""
    b = (np.asarray(bits, U32) & U32(0xFF)) >> U32(1)
    return b.astype(np.float32) / np.float32(128.0)


def randint(key, size: int, minval: int, maxval: int):
    """``jax.random.randint(key, (size,), minval, maxval)`` for int32."""
    k1, k2 = split(key)
    idx = np.arange(size, dtype=np.uint64)
    hi_bits = bits32(k1, idx).astype(np.uint64)
    lo_bits = bits32(k2, idx).astype(np.uint64)
    span = np.uint64(maxval - minval if maxval > minval else 1)
    # 2**32 mod span, squared in uint32 as JAX does: past 2**16 it wraps
    mult = np.uint64(((2 ** 16) % int(span)) ** 2 % 2 ** 32 % int(span))
    off = ((hi_bits % span) * mult % np.uint64(2 ** 32)
           + lo_bits % span) % np.uint64(2 ** 32) % span
    return (minval + off.astype(np.int64)).astype(np.int32)


def permutation(key, n: int):
    """``jax.random.permutation(key, n)``: rounds of a stable sort by
    fresh 32-bit keys."""
    rounds = int(np.ceil(3 * np.log(max(1, n)) / np.log(_MASK)))
    x = np.arange(n, dtype=np.int32)
    idx = np.arange(n, dtype=np.uint64)
    for _ in range(rounds):
        key, sub = split(key)
        order = np.argsort(bits32(sub, idx), kind="stable")
        x = x[order]
    return x
