"""Reduction of one profiler trace of the window to numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<i>``; their
``XLA Ops`` line holds one event per operation run on the chip.  The host
plane holds the benchmark's own spans (``bench.*``, written with
``TraceAnnotation`` by ``bench/drive.py``), on the same clock; the
``bench.window`` span bounds the window.

* busy: the union of the operation intervals of a chip inside the window;
  idle is the rest.  Both are averaged over the chips the cell uses.
* each of the longest idle gaps of the first chip is put down to the host
  span that overlaps it most (``host idle`` where none does).
* program time (the ``XLA Modules`` line), summed over chips and divided
  by their number.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

GAPS = 10          # the longest idle gaps put down to host spans


def find_xplane(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(busy, lo: int, hi: int) -> List[Tuple[int, int]]:
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def reduce_trace(path: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.end_ns)))
    windows = [(a, b) for name, a, b in host if name == "bench.window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo, hi = windows[0]
    devices = sorted(devices, key=lambda p: p.name)[:chips]
    busy_ns = []
    programs: Dict[str, int] = {}
    first_gaps: List[Tuple[int, int]] = []
    for i, plane in enumerate(devices):
        spans = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    a, b = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
                    if b > a:
                        name = ev.name.split("(")[0]
                        programs[name] = programs.get(name, 0) + (b - a)
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                a, b = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
                if b <= a:
                    continue
                spans.append((a, b))
        busy = _union(spans)
        busy_ns.append(sum(b - a for a, b in busy))
        if i == 0:
            first_gaps = _gaps(busy, lo, hi)
    n = max(len(devices), 1)
    gaps = []
    for a, b in sorted(first_gaps, key=lambda g: g[0] - g[1])[:GAPS]:
        best, name = 0, "host idle"
        for hname, ha, hb in host:
            ov = min(b, hb) - max(a, ha)
            if hname != "bench.window" and ov > best:
                best, name = ov, hname
        gaps.append((name, (b - a) / 1e9))
    return dict(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / n / 1e9,
        devices=len(devices),
        programs_s={k: v / n / 1e9 for k, v in programs.items()},
        gaps=gaps)


def breakdown(red: dict) -> dict:
    """The device's programs that took most time (``XLA Modules``: one
    event per compiled program run, so nothing is counted twice) and the
    longest idle gaps with what the host was doing."""
    ops = sorted(red["programs_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(red["gaps"], key=lambda g: -g[1])[:10]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[k, v] for k, v in gaps])
