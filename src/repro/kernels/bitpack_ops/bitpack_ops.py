"""Bit-packed or-and matmul Pallas kernel (TPU target) — beyond-paper opt.

The paper counts rvset traffic in *bits* (Theorem 1: |V_f| equations of
|V_f| bits).  Packing 32 boundary nodes per uint32 lane makes the engine
match that accounting exactly: the all-gathered boundary matrix and the
closure working set shrink 32x, and the or-and contraction becomes

    C[i, j] = OR_w ( Apacked[i, w] AND Bpacked[w, j] ) != 0

— pure VPU bitwise ops, 32 contraction steps per loaded word, leaving the
MXU idle.  The engine uses the packing for its collective payloads
(``ops.pack_payload``); this kernel is not on the served path, and its
crossover against ``bool_matmul`` has not been measured on a chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import out_vma


def pack_rows(a: jax.Array) -> jax.Array:
    """[M, K] bool -> [M, ceil(K/32)] uint32 (bit b of word w = a[:, 32w+b])."""
    M, K = a.shape
    W = (K + 31) // 32
    pad = W * 32 - K
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
    bits = a.reshape(M, W, 32).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights[None, None, :], axis=-1, dtype=jnp.uint32)


def pack_cols(b: jax.Array) -> jax.Array:
    """[K, N] bool -> [ceil(K/32), N] uint32 (bit b of word w = b[32w+b, :])."""
    return pack_rows(b.T).T


def unpack_rows(ap: jax.Array, K: int) -> jax.Array:
    """Inverse of pack_rows."""
    M, W = ap.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (ap[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(M, W * 32)[:, :K].astype(bool)


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, cw: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                       # [bm, bw] uint32
    b = b_ref[...]                       # [bw, bn] uint32
    bw = a.shape[1]
    # one packed word per step: broadcast a's word column along the lanes
    # and b's word row along the sublanes (static unroll: the TPU lowering
    # has no value-level dynamic slice); cw words OR into a partial hit
    # before it meets the accumulator
    acc = acc_ref[...]
    for c0 in range(0, bw, cw):
        hit = a[:, c0:c0 + 1] & b[c0:c0 + 1, :]           # [bm, bn]
        for c in range(c0 + 1, c0 + cw):
            hit = hit | (a[:, c:c + 1] & b[c:c + 1, :])
        acc = acc | hit
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] != 0


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bw", "cw", "interpret"))
def bitpack_matmul_pallas(ap: jax.Array, bp: jax.Array, *, bm: int = 128,
                          bn: int = 128, bw: int = 128, cw: int = 8,
                          interpret: bool = False) -> jax.Array:
    """ap [M, W] uint32 (row-packed), bp [W, N] uint32 (col-packed) ->
    or-and product [M, N] bool.  ``bw`` packed words per block sit on the
    lane axis of ``ap``'s block, so the TPU needs ``bw`` = 128 or ``W``."""
    M, W = ap.shape
    W2, N = bp.shape
    assert W == W2 and M % bm == 0 and N % bn == 0 and W % bw == 0
    assert bw % cw == 0
    k_steps = W // bw
    grid = (M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, cw=cw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bw, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        # inside shard_map the output varies over the mesh axes its
        # operands vary over (shard_map checks this)
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bool_,
                                       vma=out_vma(ap, bp)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.uint32)],
        interpret=interpret,
        name="bitpack_matmul",
    )(ap, bp)
