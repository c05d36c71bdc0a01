"""The cell of ``BENCHMARK.json`` at a tiny size on the CPU prints the
contract's result line and compares sound; its control comparison fails;
the command refuses without a TPU; a cell, traffic mix, configuration and
metric added as files alone are picked up; and the window closes on a
batch's last answer."""
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from bench_util import ADDED, ONE, ROOT, add_cell, run_child

from bench import drive

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)


@pytest.fixture(scope="module", params=[ONE])
def cell_run(request, tmp_path_factory):
    info, result = run_child(request.param, tmp_path_factory.mktemp("cell"),
                             "--control", "no_exchange")
    return info, result


def test_cell_prints_contract_line(cell_run):
    info, result = cell_run
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in DOC["end_to_end"]
            if "workloads" not in m or ONE in m["workloads"]}
    assert set(result["metrics"]) == want
    assert result["device"]["count"] == 1
    assert info["checked"] > 0 and info["window_s"] >= 3.0
    # the window holds whole batches: every read of a batch it saw complete
    qps = result["metrics"]["query_qps"]["value"]
    assert info["window_batches"] > 1
    assert round(qps * info["window_s"]) == info["window_batch_reads"]


def test_control_comparison_fails(cell_run):
    """The comparison tells the control (the reference with one stated
    guarantee broken) from the program."""
    info, _ = cell_run
    readings = info["control_checks"]["no_exchange"]
    assert readings["answer_mismatches"] > 0, readings


def _run_bench(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ONE, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(where, tmp_path):
    """No TPU, or only the benchmark's own files: non-zero exit and no
    result line."""
    cwd = ROOT
    if where == "alone":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        for p in DOC["paths"]:
            shutil.copytree(os.path.join(ROOT, p), os.path.join(cwd, p),
                            ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = _run_bench(cwd, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def _added_cell_runs(tmp_path, what):
    root = add_cell(tmp_path, what)
    info, result = run_child(ADDED, tmp_path, "--root", str(root),
                             "--control", "no_exchange")
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"setup_s", "reads_answered"}
    assert result["metrics"]["reads_answered"]["value"] > 16
    assert info["control_checks"]["no_exchange"]["answer_mismatches"] > 0


def test_cell_added_as_files_alone(tmp_path):
    """A new cell with a new traffic mix (reach and RPQ reads) and a new
    metric reader, added as files and entries, runs without a change to any
    file already there; its control fails."""
    _added_cell_runs(tmp_path, "traffic")


def test_config_added_as_files_alone(tmp_path):
    """The same with a new configuration file in place of the new mix."""
    _added_cell_runs(tmp_path, "config")


def _fut(resolved_at):
    return SimpleNamespace(fut=SimpleNamespace(
        resolved_at=resolved_at, done=lambda: resolved_at is not None))


def test_window_closes_on_a_batch():
    """The window ends at the last answer of the first batch recorded at or
    after its nominal end, not at the nominal end itself."""
    rec = drive.Record()
    now = time.monotonic()
    end = now - 5.0
    rec.batches = [(end - 1.0, 16, 16), (end + 0.3, 16, 16),
                   (end + 1.3, 16, 16)]
    sent = ([_fut(end - 0.999)] * 16            # the batch before the end
            + [_fut(end + 0.3001 + i * 1e-4) for i in range(16)]
            + [_fut(end + 1.3002)] * 16 + [_fut(None)] * 16)
    assert drive.close_window(rec, sent, end) == pytest.approx(end + 0.3016)
    # no batch after the closing one yet: its answers so far count
    rec.batches = rec.batches[:2]
    assert drive.close_window(rec, sent[:32], end) == \
        pytest.approx(end + 0.3016)
    # no batch at all at or after the end
    assert drive.close_window(rec, sent, end + 10.0, wait_s=0.01) == \
        end + 10.0
