"""Cell programs: the unit the launch tests lower and compile.

A *cell* is one (architecture x input-shape) combination.  Each family
adapter builds a ``CellProgram``: a step function, abstract (ShapeDtypeStruct)
arguments, and PartitionSpec pytrees for the production mesh.  The same
machinery, with ``reduced=True``, yields a tiny concrete configuration that
the smoke tests actually execute on CPU.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from ...optim import adamw


@dataclasses.dataclass
class CellProgram:
    arch_id: str
    shape_id: str
    kind: str                      # train | prefill | decode | serve | retrieval
    step_fn: Callable              # positional-args function to lower
    abstract_args: Tuple           # pytrees of jax.ShapeDtypeStruct
    arg_specs: Tuple               # matching pytrees of PartitionSpec
    model_flops: float             # analytic useful FLOPs (6*N*D style)
    model_bytes: float             # analytic minimum HBM traffic (params+state)
    notes: str = ""
    # cost-probe support: XLA cost_analysis counts loop bodies once, so
    # probes lower loop-free variants and multiply by cost_scale (e.g. the
    # grad-accumulation factor, or the serve_bulk chunk count).
    cost_scale: float = 1.0


def dp(multipod: bool):
    """Data-parallel mesh axes (pod composes with data across pods)."""
    return ("pod", "data") if multipod else ("data",)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def abstract_like(tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)


def spec_tree(tree, fn):
    """Build a PartitionSpec pytree via fn(path_string, leaf)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(jax.tree_util.keystr(path), leaf), tree)


OPT_CFG = adamw.AdamWConfig(lr=1e-4, warmup_steps=200, total_steps=50_000)


def make_train_step(loss_fn, accum: bool):
    """Standard production train step: (params, m, v, step, *batch) ->
    (params, m, v, step, loss).  With ``accum`` the leading batch axis is
    scanned as microbatches (gradient accumulation)."""

    def step(params, m, v, stepno, *batch):
        if accum:
            def micro(c, mb):
                l, g = jax.value_and_grad(loss_fn)(params, *mb)
                return (c[0] + l, jax.tree.map(jnp.add, c[1], g)), None
            zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                params)
            n = jax.tree.leaves(batch)[0].shape[0]
            (loss, grads), _ = jax.lax.scan(micro, (jnp.float32(0), zero),
                                            batch)
            loss, grads = loss / n, jax.tree.map(lambda g: g / n, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        state = adamw.AdamWState(step=stepno, m=m, v=v)
        params, state, _ = adamw.update(OPT_CFG, grads, state, params)
        return params, state.m, state.v, state.step, loss

    return step


def opt_state_like(params_abs):
    f32 = lambda t: jax.tree.map(lambda s: sds(s.shape, jnp.float32), t)
    return f32(params_abs), f32(params_abs), sds((), jnp.int32)


def zeros_from_abstract(tree, seed: int = 0):
    """Materialize concrete arrays for smoke tests: small random floats,
    zeros for ints/bools (always-valid indices)."""
    key = jax.random.key(seed)
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, s in enumerate(leaves):
        if jnp.issubdtype(s.dtype, jnp.floating):
            # non-negative so optimizer second moments stay valid
            out.append(jnp.abs(jax.random.normal(
                jax.random.fold_in(key, i), s.shape, s.dtype)) * 0.05)
        else:
            out.append(jnp.zeros(s.shape, s.dtype))
    return treedef.unflatten(out)
