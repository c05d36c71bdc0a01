"""Pure-jnp oracle for the (min, +) matmul."""
import jax.numpy as jnp
import numpy as np

INF = np.int32(1 << 29)   # numpy: importing must not start a backend


def tropical_matmul_ref(a, b):
    """a [M, K], b [K, N] int32 -> min_k(a + b) [M, N], INF-saturated."""
    out = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
    return jnp.minimum(out, INF).astype(jnp.int32)
