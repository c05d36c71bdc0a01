"""Pallas kernels for the semiring hot spots the engine runs on a TPU:
or-and (``bool_matmul``), min-plus (``tropical_matmul``) and the
bit-packed or-and (``bitpack_ops``)."""
import jax


def on_tpu() -> bool:
    """The one backend switch: on a TPU the semiring dispatchers run the
    Pallas kernels compiled; elsewhere they take the XLA fallbacks, and a
    kernel called directly runs in Pallas interpret mode."""
    return jax.default_backend() == "tpu"


def out_vma(*operands) -> frozenset:
    """The mesh axes a kernel's output varies over: those its operands
    vary over.  Inside ``shard_map`` (which checks this) a ``pallas_call``
    must declare it on its ``out_shape``; outside, it is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))
