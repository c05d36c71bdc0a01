"""Readings of the comparison's control, and of sound runs beside them.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

On the chip, in one process: one run of the cell per seed, exactly as
``bench/run.py`` makes it, whose answers are compared twice -- as a run
compares them, and with the control answers in their place: the plain
reference with one guarantee of the configuration broken
(``no_exchange``: answered without the edges between different
fragments, as if the sites' partial answers were never combined).  A
sound run reads 0 on every number; the control has to read above 0 on at
least one, or the comparison could not tell it from the program.  Prints
one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from bench.run import run_cell
    mode = "no_exchange"
    for seed in args.seeds:
        result, info = run_cell(args.workload, seed, args.seconds, False,
                                controls=(mode,), t_start=time.monotonic())
        print(json.dumps(dict(
            seed=seed, correct=result["correct"],
            sound={k: v["value"] for k, v in result["checks"].items()},
            control=mode, control_readings=info["control_checks"][mode],
            metrics={k: v["value"] for k, v in result["metrics"].items()},
            checked=info["checked"])),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
