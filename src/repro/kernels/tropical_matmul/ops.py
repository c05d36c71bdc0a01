"""Jit'd public wrapper: INF-pads to block multiples; interpret off-TPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import on_tpu
from .tropical_matmul import INF, tropical_matmul_pallas

# rows of ``a`` per step of the fallback: caps its broadcast intermediate
# at ROW_CHUNK * K * N int32
ROW_CHUNK = 16


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=int(INF))
    return x


@functools.partial(jax.jit, static_argnames=("block",))
def tropical_matmul(a: jax.Array, b: jax.Array, block: int = 128) -> jax.Array:
    M, N = a.shape[0], b.shape[1]
    a = _pad_to(a.astype(jnp.int32), block, block)
    b = _pad_to(b.astype(jnp.int32), block, block)
    out = tropical_matmul_pallas(a, b, bm=block, bn=block, bk=block,
                                 interpret=not on_tpu())
    return out[:M, :N]


def min_plus_chunked(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pure-jnp row-chunked (min, +) contraction: chunking caps the
    [C, K, N] broadcast intermediate so the closure of a few-thousand-node
    boundary stays well under a GiB.  The single shared fallback for every
    non-TPU min-plus path (bes closures, evalDG_d, batched dist)."""
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    M, K = a.shape
    if M == 0 or K == 0:        # empty contraction: min over nothing == INF
        return jnp.full((M, b.shape[1]), INF, jnp.int32)

    def one_chunk(rows):
        return jnp.min(rows[:, :, None] + b[None, :, :], axis=1)

    if M <= ROW_CHUNK:
        return jnp.minimum(one_chunk(a), INF)
    pad = (-M) % ROW_CHUNK
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=int(INF))
    chunks = a.reshape(-1, ROW_CHUNK, K)
    out = jax.lax.map(one_chunk, chunks)
    return jnp.minimum(out.reshape(-1, b.shape[1])[:M], INF)


def min_plus_matmul(a: jax.Array, b: jax.Array,
                    block: int = 128) -> jax.Array:
    """Backend-dispatched (min, +) contraction C = min_k (a + b):
    the Pallas tropical kernel on TPU, :func:`min_plus_chunked` elsewhere."""
    if on_tpu():
        return tropical_matmul(a, b, block=block)
    return min_plus_chunked(a, b)
