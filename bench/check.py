"""The comparison that decides ``correct``.

Every number compared is a count of faults whose limit is 0:

* ``answer_mismatches``: a sample of the window's answers, drawn from the
  seed, each compared with the plain reference (``bench/reference.py``)
  on the graph the run built.
* ``unknown_snapshots``: answers stamped with another version than the
  one the server started on (the traffic writes nothing, so no other
  version exists).

``control`` swaps the program's answers for the reference's with one
stated guarantee broken -- ``no_exchange``: answered on the graph without
the edges between different fragments, as if the sites' partial answers
were never combined -- and must fail.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from . import reference
from . import workload as wl


def compare(sent, g: wl.Graph, tr: dict, seed: int, base_version: int,
            t1: float, control: Optional[str] = None) -> Dict[str, int]:
    # the window's answers: those that came within it
    reads = [s for s in sent if str(s.fut.status) == "done"
             and s.fut.resolved_at <= t1]
    out = dict(unknown_snapshots=sum(s.fut.cache_version != base_version
                                     for s in reads))
    rng = np.random.default_rng([seed, 4])
    take = min(tr["check_sample"], len(reads))
    sample = [reads[i] for i in sorted(rng.choice(len(reads), take,
                                                  replace=False))]
    src, dst = g.src, g.dst
    want = reference.answer_all(g.n, src, dst, g.labels,
                                [s.req for s in sample], tr["regex"])
    if control is None:
        got = [s.fut.value for s in sample]
    elif control == "no_exchange":
        keep = g.part[src] == g.part[dst]
        got = reference.answer_all(g.n, src[keep], dst[keep], g.labels,
                                   [s.req for s in sample], tr["regex"])
    else:
        raise ValueError(f"unknown control {control!r}")
    out["answer_mismatches"] = sum(
        _norm(a) != _norm(b) for a, b in zip(got, want))
    out["checked"] = len(sample)
    return out


def _norm(v):
    if v is None or isinstance(v, (bool, np.bool_)):
        return None if v is None else bool(v)
    return int(v)
