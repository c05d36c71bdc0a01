"""Or-and semiring matmul Pallas kernel (TPU target).

C[i, j] = OR_k ( A[i, k] AND B[k, j] )

This is the frontier-expansion / closure-squaring hot spot of the paper's
evalDG (DESIGN.md Sec. 2.1).  TPU mapping: 0/1 operands are upcast to f32
inside the kernel so each (bm, bk) x (bk, bn) block rides the MXU; the
accumulator stays f32 in a VMEM scratch across the K grid axis and is
thresholded (> 0) on the last K step.  Default blocks of 128 are
MXU-aligned; three f32 128x128 buffers = 192 KiB, far under VMEM.

Validated on CPU with interpret=True against ref.py (tests/test_kernels.py);
the compiled path is checked by tests/test_tpu_compile.py, which compiles
the kernel for a described v5e chip at the engine's real widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import out_vma


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(a, b,
                                preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] > 0.0


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def bool_matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                       bn: int = 128, bk: int = 128,
                       interpret: bool = False) -> jax.Array:
    """a [M, K] bool, b [K, N] bool -> [M, N] bool.  M, N, K must be
    multiples of the block sizes (ops.py pads)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        # inside shard_map the output varies over the mesh axes its
        # operands vary over (shard_map checks this)
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bool_,
                                       vma=out_vma(a, b)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="bool_matmul",
    )(a, b)
