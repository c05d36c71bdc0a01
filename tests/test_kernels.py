"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), shape sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.bool_matmul import bool_matmul, bool_matmul_ref
from repro.kernels.tropical_matmul import tropical_matmul, tropical_matmul_ref
from repro.kernels.tropical_matmul.ref import INF
from repro.kernels.bitpack_ops import (bitpack_bool_matmul,
                                       bitpack_matmul_ref, pack_rows,
                                       pack_rows_ref, unpack_rows)

SHAPES = [(128, 128, 128), (7, 200, 33), (256, 64, 128), (1, 1, 1),
          (130, 257, 5), (64, 512, 64)]
DENSITIES = [0.0, 0.02, 0.3, 1.0]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("density", DENSITIES)
def test_bool_matmul(shape, density):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    a = jnp.asarray(rng.random((m, k)) < density)
    b = jnp.asarray(rng.random((k, n)) < density)
    np.testing.assert_array_equal(np.asarray(bool_matmul(a, b)),
                                  np.asarray(bool_matmul_ref(a, b)))


@pytest.mark.parametrize("shape", SHAPES)
def test_tropical_matmul(shape):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = rng.integers(0, 50, (m, k)).astype(np.int32)
    b = rng.integers(0, 50, (k, n)).astype(np.int32)
    # sprinkle INF entries (absent edges)
    a[rng.random((m, k)) < 0.3] = int(INF)
    b[rng.random((k, n)) < 0.3] = int(INF)
    got = np.asarray(tropical_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(tropical_matmul_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_bitpack_matmul(shape, density):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**30)
    a = jnp.asarray(rng.random((m, k)) < density)
    b = jnp.asarray(rng.random((k, n)) < density)
    got = np.asarray(bitpack_bool_matmul(a, b))
    want = np.asarray(bitpack_matmul_ref(a, b))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [1, 31, 32, 33, 100, 256])
def test_pack_roundtrip(k):
    rng = np.random.default_rng(k)
    a = jnp.asarray(rng.random((17, k)) < 0.4)
    packed = pack_rows(a)
    np.testing.assert_array_equal(np.asarray(packed), pack_rows_ref(a))
    np.testing.assert_array_equal(np.asarray(unpack_rows(packed, k)),
                                  np.asarray(a))


def test_closure_with_pallas_matches_ref(monkeypatch):
    """End-to-end: bes closures using the kernels == pure-jnp closures.
    Off-TPU the dispatchers take the XLA fallbacks; pointing them at the
    Pallas kernels (interpret mode here) runs the closures as on a TPU."""
    import repro.kernels.bool_matmul.ops as bops
    import repro.kernels.tropical_matmul.ops as tops
    from repro.core.bes import bool_closure, tropical_closure
    rng = np.random.default_rng(0)
    D = jnp.asarray(rng.random((50, 50)) < 0.05)
    W = rng.integers(0, 9, (40, 40)).astype(np.int32)
    W[rng.random((40, 40)) < 0.6] = int(INF)
    want_d = bool_closure(D)
    want_w = tropical_closure(jnp.asarray(W))
    monkeypatch.setattr(bops, "or_and_matmul", bops.bool_matmul)
    monkeypatch.setattr(tops, "min_plus_matmul", tops.tropical_matmul)
    np.testing.assert_array_equal(np.asarray(bool_closure(D)),
                                  np.asarray(want_d))
    got = tropical_closure(jnp.asarray(W))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_w))
