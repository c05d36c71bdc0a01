"""Test config. NOTE: do NOT set XLA_FLAGS / fake device counts here —
smoke tests must see the single real CPU device.  Multi-device tests
spawn subprocesses that set XLA_FLAGS before importing jax."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

# Property tests use hypothesis when installed; otherwise fall back to the
# deterministic stub so the suite still runs in hermetic environments.
try:
    import hypothesis  # noqa: F401
except ImportError:
    import _hypothesis_stub
    _hypothesis_stub.install(sys.modules)


# Dropping JAX's caches between modules keeps the set of live compiled
# executables in one process small (each module pays only its own warm-up
# again).  It was added against a jaxlib CPU JIT that segfaulted in
# backend_compile once a process held ~200 tests' executables; it also
# bounds the memory a long single-process run holds.
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    import jax
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _runtime_lock_order(request):
    """Under the chaos/mvcc suites, run every session/store/engine built
    by the test on instrumented locks and fail on any acquisition-order
    inversion (DESIGN.md Sec. 10.3, rules LCK001-003)."""
    marks = {m.name for m in request.node.iter_markers()}
    if not marks & {"chaos", "mvcc"}:
        yield
        return
    from repro.analysis.locks import monitored
    with monitored() as mon:
        yield
    assert not mon.violations, [str(v) for v in mon.violations]
