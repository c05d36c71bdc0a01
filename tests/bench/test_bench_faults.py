"""A run whose served path is broken underneath the harness comes out not
correct: an answer altered where it is made, for reach answers and for
distances (exact and bounded reads)."""
import pytest

from bench_util import ONE, run_child


@pytest.mark.parametrize("cell,fault", [(ONE, "answer"), (ONE, "distance")])
def test_fault_makes_run_incorrect(cell, fault, tmp_path):
    _, result = run_child(cell, tmp_path, "--fault", fault)
    assert result["correct"] is False, result["checks"]
    bad = {k for k, v in result["checks"].items() if v["value"] > v["limit"]}
    assert bad == {"answer_mismatches"}, result["checks"]
