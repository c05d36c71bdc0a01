"""The planner's reach-fused share (``bench/metrics/reach_fused_share.py``),
read from the session's ``reach_rows`` and ``reach_fused`` counters."""
import pytest

from bench_util import ONE, ROOT, run_child

from bench import spec


def test_reach_fused_share_reads_the_session_counters():
    read = spec.Spec(ROOT).reader("reach_fused_share")
    assert read({"stats": {"reach_rows": 64, "reach_fused": 48}}) == 75.0
    assert read({"stats": {"reach_rows": 22, "reach_fused": 22}}) == 100.0
    # no reach read answered, or a session without the counters: nothing
    # to read
    assert read({"stats": {"reach_rows": 0, "reach_fused": 0}}) is None
    assert read({"stats": {"queries": 64, "batches": 1}}) is None


def test_traced_run_reports_reach_fused_share(tmp_path):
    info, result = run_child(ONE, tmp_path, trace=1)
    assert result["correct"] is True, result["checks"]
    stats = info["session_stats"]
    fused = result["metrics"]["reach_fused_share"]["value"]
    assert fused == pytest.approx(
        100.0 * stats["reach_fused"] / stats["reach_rows"])
    assert 0.0 < fused <= 100.0
