"""CPU rehearsal of ``chip_smoke.py``: its one-chip serving phase and its
four-chip mesh phase at a tiny size (the latter on fake CPU devices), and
its refusal to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
import repro  # noqa: E402
from repro.core import fragment_graph  # noqa: E402


def test_serving_phase_matches_oracle_on_both_paths(capsys):
    """Barrier and MVCC serving of mixed requests across one delta: every
    answer equals the oracle on the snapshot it names, nothing degraded,
    retried, dead-lettered or rolled back, and every kind is seen on both
    sides of the delta."""
    k = 4
    g, part = chip_smoke.build_graph(seed=3, k=k, block=64, degree=2,
                                     n_cross=16)
    fr = fragment_graph(g, part, k, reserve_boundary=8, reserve_edges=8,
                        reserve_stubs=8)
    session = repro.connect(fr, backend="vmap").warm(with_dist=True)
    failures, observed = chip_smoke.barrier_and_mvcc(
        session, g, part, n_requests=48, seed=3)
    assert failures == [], failures
    seen = {(req[0], snap) for req, snap, _ in observed}
    assert seen == {(kind, snap)
                    for kind in ("reach", "dist", "bounded", "rpq")
                    for snap in ("pre", "post")}, seen
    assert "serve_mvcc:" in capsys.readouterr().out


_FOUR_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, __ROOT__)
import jax, chip_smoke
failures = chip_smoke.four_chips(0, jax.devices(), k=8, block=64, degree=2,
                                 n_cross=24)
print("FAILURES", len(failures), failures)
"""


def test_four_chip_phase_on_fake_devices():
    """The ``--chips 4`` phase at a tiny size on 4 fake CPU devices:
    shard_map serving across a delta repaired by apply_delta_sharded,
    oracle-exact, nothing degraded, and the guarantee verifier clean."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES.replace("__ROOT__", repr(ROOT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"update": "repair_sharded"' in out.stdout, out.stdout
    assert out.stdout.strip().splitlines()[-1] == "FAILURES 0 []", out.stdout


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(where, tmp_path):
    """No TPU (``JAX_PLATFORMS=cpu``), or no repository around the script:
    non-zero exit and no ``ok`` line."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    out = _run(cwd)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
