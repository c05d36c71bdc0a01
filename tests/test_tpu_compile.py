"""The Pallas kernels compiled for a TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology.  These tests compile each kernel
itself with ``interpret=False`` at the engine's real widths -- the public
wrappers would take their CPU branch here -- and require the Mosaic
kernel (``tpu_custom_call``) in the compiled program.  That catches what
interpret mode cannot: block shapes the TPU refuses and primitives it
cannot lower.

The topology is described in a fixture of this file only, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist only the worker given this file does.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cache import _batch_dist_kernel
from repro.kernels.bitpack_ops.bitpack_ops import bitpack_matmul_pallas
from repro.kernels.bool_matmul.bool_matmul import bool_matmul_pallas
from repro.kernels.tropical_matmul.tropical_matmul import \
    tropical_matmul_pallas

SIDE = 8192        # boundary-matrix side: closure squaring is [SIDE, SIDE]^2
ROWS = 128         # a padded batch: the combine is [ROWS, SIDE] x [SIDE, SIDE]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, dtype, sharding):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]
    return jax.jit(lambda a, b: fn(a, b, interpret=False)).lower(
        *args).compile().as_text()


SEMIRING_KERNELS = {"or_and": (bool_matmul_pallas, jnp.bool_),
                    "min_plus": (tropical_matmul_pallas, jnp.int32)}


@pytest.mark.parametrize("kernel", sorted(SEMIRING_KERNELS))
@pytest.mark.parametrize("use,shapes", [
    ("closure", ((SIDE, SIDE), (SIDE, SIDE))),
    ("combine", ((ROWS, SIDE), (SIDE, SIDE))),
])
def test_semiring_kernel_compiles_for_v5e(kernel, use, shapes, one_chip):
    fn, dtype = SEMIRING_KERNELS[kernel]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, dtype, one_chip)


def test_bitpack_kernel_compiles_for_v5e(one_chip):
    """32 boundary nodes per uint32 word: a SIDE-wide Boolean closure
    packs to SIDE // 32 words on the contraction axis."""
    words = SIDE // 32
    text = _compiled_text(bitpack_matmul_pallas,
                          ((SIDE, words), (words, SIDE)), jnp.uint32,
                          one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fn,dtype,shapes,name", [
    (bool_matmul_pallas, jnp.bool_, ((256, 256), (256, 256)),
     "bool_matmul"),
    (tropical_matmul_pallas, jnp.int32, ((256, 256), (256, 256)),
     "tropical_matmul"),
    (bitpack_matmul_pallas, jnp.uint32, ((256, 128), (128, 256)),
     "bitpack_matmul"),
])
def test_kernel_named_in_compiled_program(fn, dtype, shapes, name,
                                          one_chip):
    """Each Pallas call carries its kernel's name, which names its op in a
    device trace."""
    text = _compiled_text(fn, shapes, dtype, one_chip)
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text), name


# the benchmark graph's widths (bench/configs/er16x16k.json): fragments,
# edge slots and local slots per fragment, boundary nodes; a full batch
K, E_MAX, N_MAX, NB = 16, 66048, 16768, 3904
BATCH = 64

_GATHER = re.compile(r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]+)\}")


def _gathers(text):
    """``(result shape, slice sizes)`` of every gather in a compiled
    program."""
    out = []
    for line in text.splitlines():
        m = _GATHER.search(line)
        if m:
            out.append((tuple(int(d) for d in m[1].split(",") if d),
                        tuple(int(d) for d in m[2].split(","))))
    return out


def test_batch_local_stage_gathers_rows_not_scalars(one_chip):
    """The batched dist program at the benchmark's widths relaxes its rows
    as lanes: each round gathers whole N-wide rows of the frontier over
    the union edge list, and no gather fetches one scalar per edge slot
    (per row, as the per-row layout did: 4.2M scalar accesses a round).
    The gathers of the answers' boundary entries, [N, nb] scalars, stay."""
    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    text = _batch_dist_kernel.lower(
        arg((K, E_MAX)), arg((K, E_MAX)), arg((K, NB + 2)),
        arg((NB, N_MAX + 1)), arg((NB, NB)), None, arg((BATCH,)),
        arg((BATCH,)), arg((BATCH,)), arg((BATCH, NB)),
        n_max=N_MAX).compile().as_text()
    gathers = _gathers(text)
    scalar = [shape for shape, sizes in gathers
              if set(sizes) == {1} and max(shape, default=0) >= E_MAX]
    assert not scalar, scalar
    assert ((K * E_MAX, BATCH), (1, BATCH)) in gathers, gathers
