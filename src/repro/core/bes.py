"""Boolean-equation-system / dependency-graph closure utilities.

The paper solves the assembled BES (a disjunctive system [14]) by
reachability on the dependency graph G_d.  Two regimes:

* single query  -> single-source fixpoint (``engine.evaldg_*``), O(diam B^2);
* many queries / reusable fragmentation -> **all-pairs closure** by repeated
  squaring: ceil(log2 B) semiring matmuls on the MXU.  Amortizes across a
  query workload; also the target of the Pallas kernels
  (``repro.kernels.bool_matmul`` / ``tropical_matmul`` / ``bitpack_ops``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .engine import INF


def _ceil_log2(b: int) -> int:
    return max(1, math.ceil(math.log2(max(b, 2))))


def bool_closure(D):
    """Reflexive-transitive closure of a Boolean matrix [B, B].

    A := A | A@A, repeated ceil(log2 B) times over A = D | I.  The products
    go through the backend dispatcher ``or_and_matmul`` (MXU kernel on TPU,
    f32 matmul elsewhere).
    """
    from ..kernels.bool_matmul import ops as bops
    B = D.shape[-1]
    A = D | jnp.eye(B, dtype=bool)
    if B == 0:
        return A

    # squaring doubles covered path length: fixpoint after ceil(log2 diam)
    # rounds, capped at ceil(log2 B) (worst case diam == B)
    def cond(state):
        _, i, changed = state
        return changed & (i < _ceil_log2(B))

    def body(state):
        A, i, _ = state
        A2 = A | bops.or_and_matmul(A, A)
        return A2, i + 1, jnp.any(A2 != A)

    A, _, _ = jax.lax.while_loop(cond, body, (A, jnp.int32(0), jnp.bool_(True)))
    return A


def tropical_closure(W):
    """Min-plus closure of a distance matrix [B, B] (diag forced to 0).

    W := min(W, W (min,+) W), repeated ceil(log2 B) times.  The products
    go through the backend dispatcher ``min_plus_matmul`` (Pallas kernel
    on TPU; elsewhere a fallback that chunks rows so the broadcast
    intermediate stays at ``ROW_CHUNK`` * B^2 int32, not B^3).
    """
    from ..kernels.tropical_matmul import ops as tops
    B = W.shape[-1]
    W = jnp.where(jnp.eye(B, dtype=bool), 0, W).astype(jnp.int32)

    if B == 0:
        return W

    def cond(state):
        _, i, changed = state
        return changed & (i < _ceil_log2(B))

    def body(state):
        W, i, _ = state
        W2 = jnp.minimum(jnp.minimum(W, tops.min_plus_matmul(W, W)), INF)
        return W2, i + 1, jnp.any(W2 != W)

    W, _, _ = jax.lax.while_loop(cond, body, (W, jnp.int32(0), jnp.bool_(True)))
    return W


def closure_answers(A, src_rows, tgt_cols):
    """Batch answer extraction: ans[q] = any A[src[q], tgt[q]] for index
    arrays src_rows/tgt_cols [nq]."""
    return A[src_rows, tgt_cols]
