"""Shared helpers of the benchmark's tests: run a cell tiny on the CPU in
a child process (fresh JAX, its own compile cache)."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CHILD = os.path.join(os.path.dirname(__file__), "bench_child.py")
ONE = "er16x16k.reachdist_closed"
ADDED = "er16x16k.reach_rpq_closed"     # a cell added as files, below
SEED = 2**31 + 11          # seeds may pass 32 signed bits


def run_child(cell, tmp_path, *args, seconds=3.0, trace=0, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, CHILD, cell, str(SEED), str(seconds), str(trace),
         *args], env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def add_cell(tmp_path, what: str):
    """A copy of the benchmark with one cell added as files and entries
    alone, and a metric reader of its own; returns the copy's root.
    ``what`` is ``traffic`` (a new mix of reach and RPQ reads on the
    existing configuration) or ``config`` (the existing mix on a new
    configuration with four labels)."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    config, traffic = "er16x16k", "reachdist_closed"
    if what == "traffic":
        traffic = "reach_rpq_closed"
        mix = dict(outstanding=256, kinds=["reach", "rpq"], pool=512,
                   walk_share=0.5, walk_steps=[1, 6], bound=[2, 15], regex="0*",
                   regex_label=0, check_sample=48, result_wait_s=60.0)
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(mix))
    else:
        config = "er16x16k-l4"
        with open(os.path.join(ROOT, "bench", "configs",
                               "er16x16k.json")) as f:
            cfg = json.load(f)
        cfg.update(name=config, labels=4)
        (root / "bench" / "configs" / f"{config}.json").write_text(
            json.dumps(cfg))
        doc["configs"].append(dict(doc["configs"][0], name=config,
                                   file=f"bench/configs/{config}.json"))
    (root / "bench" / "metrics" / "reads_answered.py").write_text(
        "def read(run):\n"
        "    return sum(str(s.fut.status) == 'done' for s in run['sent'])\n")
    doc["workloads"].append(dict(name=ADDED, config=config, traffic=traffic,
                                 chips=1, why="test"))
    doc["end_to_end"].append(dict(
        name="reads_answered", unit="reads", better="higher", bound=0.25,
        source="host_clock", workloads=[ADDED]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root
