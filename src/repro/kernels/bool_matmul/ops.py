"""Jit'd public wrapper: pads to block multiples, picks interpret mode on CPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import on_tpu
from .bool_matmul import bool_matmul_pallas


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("block",))
def bool_matmul(a: jax.Array, b: jax.Array, block: int = 128) -> jax.Array:
    """Or-and matmul with automatic padding; interpret=True off-TPU."""
    M, N = a.shape[0], b.shape[1]
    bm = bn = bk = block
    a = _pad_to(a.astype(bool), bm, bk)
    b = _pad_to(b.astype(bool), bk, bn)
    out = bool_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk,
                             interpret=not on_tpu())
    return out[:M, :N]


def or_and_matmul(a: jax.Array, b: jax.Array, block: int = 128) -> jax.Array:
    """Backend-dispatched or-and contraction C = OR_k (a & b).

    The rvset cache / evalDG hot path routes through here: on TPU the MXU
    Pallas kernel runs compiled; elsewhere the same semiring is one XLA f32
    matmul + threshold (interpret-mode Pallas would be orders of magnitude
    slower on CPU, so it is reserved for the kernel unit tests).
    """
    if on_tpu():
        return bool_matmul(a, b, block=block)
    return (a.astype(jnp.float32) @ b.astype(jnp.float32)) > 0
