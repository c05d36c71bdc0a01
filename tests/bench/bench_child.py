"""Runs one benchmark cell at a tiny size on the CPU, for the tests of
``bench/``: ``python bench_child.py <cell> <seed> <seconds> <trace>
[--fault <name>] [--control <mode>] [--root <dir>]``.  Prints the run's
``info`` and result as its last two lines.

Faults break the served path underneath the harness, as a broken program
would, each altering an answer where the session makes it: ``answer``
flips reach answers, ``distance`` adds one to every distance found (exact
and bounded reads)."""
import argparse
import json
import os
import sys
import time

T = time.monotonic()
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def tiny(cfg, tr):
    """Four blocks of 64 nodes; capacities fixed as the real configuration
    fixes its own."""
    cfg.update(blocks=4, block_nodes=64, degree=2, cross_edges=24)
    cfg["capacities"].update(nb=48, n_max=96, e_max=208, s_max=24,
                             min_headroom=4)
    cfg["server"]["batch_size"] = 16
    tr["outstanding"] = 64
    tr["check_sample"] = 48
    tr["result_wait_s"] = 60.0


def plant(fault: str) -> None:
    from repro.core.session import QuerySession
    if fault == "answer":
        make = QuerySession._reach_result

        def flipped(self, q, ans, stats):
            res = make(self, q, ans, stats)
            if q.s != q.t:
                res.answer = not res.answer
            return res
        QuerySession._reach_result = flipped
    elif fault == "distance":
        make_d = QuerySession._dist_result

        def longer(self, q, d, stats):
            return make_d(self, q, d + 1 if d >= 0 else d, stats)
        QuerySession._dist_result = longer
    else:
        raise ValueError(fault)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("seed", type=int)
    p.add_argument("seconds", type=float)
    p.add_argument("trace", type=int)
    p.add_argument("--fault")
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--root", default=ROOT)
    args = p.parse_args()
    if args.fault:
        plant(args.fault)
    from bench.run import run_cell
    result, info = run_cell(args.cell, args.seed, args.seconds,
                            bool(args.trace), root=args.root,
                            require_tpu=False, override=tiny,
                            controls=tuple(args.control), t_start=T)
    print(json.dumps(info))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
