"""Tropical (min, +) semiring matmul Pallas kernel (TPU target).

C[i, j] = min_k ( A[i, k] + B[k, j] )      (int32, INF-saturating)

The disDist closure hot spot (paper Sec. 4; DESIGN.md Sec. 2.1).  There is
no MXU path for (min, +), so the kernel is VPU-shaped and two-dimensional:
each contraction step broadcasts one column of the A block along the lanes
and one row of the B block along the sublanes, adds them into a
[bm, bn] tile and folds it into the running minimum.  The bk steps are
unrolled statically (the TPU lowering has no value-level dynamic slice);
``ck`` consecutive steps fold into a partial minimum before it meets the
accumulator, so the dependency chains stay short.  The [bm, bn] int32
accumulator persists across the K grid axis in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import out_vma

INF = 1 << 29    # python int: safe to close over inside the kernel body


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, ck: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, INF)

    a = a_ref[...]                      # [bm, bk] int32
    b = b_ref[...]                      # [bk, bn] int32
    bk = a.shape[1]
    acc = acc_ref[...]
    for c0 in range(0, bk, ck):
        part = a[:, c0:c0 + 1] + b[c0:c0 + 1, :]      # [bm, bn]
        for c in range(c0 + 1, c0 + ck):
            part = jnp.minimum(part, a[:, c:c + 1] + b[c:c + 1, :])
        acc = jnp.minimum(acc, part)
    acc_ref[...] = jnp.minimum(acc, INF)              # saturate

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "ck", "interpret"))
def tropical_matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                           bn: int = 128, bk: int = 128, ck: int = 8,
                           interpret: bool = False) -> jax.Array:
    """a [M, K] int32, b [K, N] int32 -> min-plus product [M, N] int32."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and K % bk == 0 and M % bm == 0 and N % bn == 0
    assert bk % ck == 0
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, ck=ck),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        # inside shard_map the output varies over the mesh axes its
        # operands vary over (shard_map checks this)
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32,
                                       vma=out_vma(a, b)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="tropical_matmul",
    )(a, b)
