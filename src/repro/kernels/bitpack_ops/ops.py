"""Jit'd wrapper: packs Boolean operands, pads, runs the packed kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import on_tpu
from .bitpack_ops import (bitpack_matmul_pallas, pack_cols, pack_rows,
                          unpack_rows)


@functools.partial(jax.jit, static_argnames=("block",))
def bitpack_bool_matmul(a: jax.Array, b: jax.Array,
                        block: int = 128) -> jax.Array:
    """Boolean or-and matmul via 32x bit-packing.  a [M,K], b [K,N] bool."""
    M, K = a.shape
    N = b.shape[1]
    ap = pack_rows(a.astype(bool))                     # [M, W]
    bp = pack_cols(b.astype(bool))                     # [W, N]
    W = ap.shape[1]
    bw = 128               # packed words per block: the TPU's lane width
    pm, pn, pw = (-M) % block, (-N) % block, (-W) % bw
    ap = jnp.pad(ap, ((0, pm), (0, pw)))
    bp = jnp.pad(bp, ((0, pw), (0, pn)))
    out = bitpack_matmul_pallas(ap, bp, bm=block, bn=block, bw=bw,
                                interpret=not on_tpu())
    return out[:M, :N]


def pack_payload(m: jax.Array) -> jax.Array:
    """Pack a Boolean payload matrix [R, C] into uint32 words [R, ceil(C/32)]
    for the one collective in ``core.distributed`` (8x fewer bits and bytes
    on the wire than the seed's uint8-per-entry shipping)."""
    return pack_rows(m.astype(bool))


def unpack_payload(p: jax.Array, n_cols: int) -> jax.Array:
    """Inverse of :func:`pack_payload` on the replicated side."""
    return unpack_rows(p, n_cols)


def packed_bits(rows: int, cols: int) -> int:
    """Bits actually shipped for a [rows, cols] Boolean payload once packed:
    rows x ceil(cols/32) uint32 words."""
    return rows * ((cols + 31) // 32) * 32


__all__ = ["bitpack_bool_matmul", "pack_rows", "pack_cols", "unpack_rows",
           "pack_payload", "unpack_payload", "packed_bits"]
