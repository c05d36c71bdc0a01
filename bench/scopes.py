"""Device time per named scope, and idle gaps named by the program's own
host spans, from one profiler trace of the window; and the per-batch
readings built on them.

The program marks the stages of its batch programs with named scopes
(``local_stage``, ``gather``, ``combine``, ``closure``, ``collective``:
``repro.core.cache``, ``repro.core.distributed``) and its layer boundaries
with host spans (``repro.*``: ``repro.tracing``).  A scope reaches the
trace only in the ``tf_op`` stat of each ``XLA Ops`` event's metadata,
which ``jax.profiler.ProfileData`` does not expose; so this module reads
the few fields it needs from the XSpace's protobuf wire format itself.

* each instant of an op's time goes to the innermost op running then (a
  ``while`` contains its body's ops), and to the first scope on that op's
  ``tf_op`` path, else to ``unscoped``; so the scopes add up to the busy
  time of ``bench/xplane.py``, chip by chip;
* ``program_scopes_s`` splits each program of the ``XLA Modules`` line
  (its name cut at ``(``, as ``bench/xplane.py`` has it) the same way;
* each of the longest idle gaps of the first chip is put down to the
  ``bench.*`` or ``repro.*`` host span that overlaps it most, ties going to
  the shorter, inner span (``host idle`` where none does).

A trace of a program without scopes or spans reads all its time
``unscoped`` and names its gaps by the benchmark's spans alone.
"""
from __future__ import annotations

import gzip
from typing import Dict, List, Optional, Tuple

from bench.xplane import GAPS, _gaps, _union

SCOPES = ("local_stage", "gather", "combine", "closure", "collective")
UNSCOPED = "unscoped"
HOST_PREFIXES = ("bench.", "repro.")
DEVICE_LINES = ("XLA Modules", "XLA Ops")
WINDOW = "bench.window"


# ---------------------------------------------------------------------------
# the XSpace wire format: the fields read, by number
# ---------------------------------------------------------------------------
# XSpace.planes 1; XPlane: name 2, lines 3, event_metadata 4 (map entry:
# key 1, value 2), stat_metadata 5; XLine: name 2, timestamp_ns 3,
# events 4; XEvent: metadata_id 1, offset_ps 2, duration_ps 3;
# XEventMetadata: name 2, stats 5; XStat: metadata_id 1, str_value 5,
# ref_value 7; XStatMetadata: name 2.

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message: an int for varints, a
    ``(start, end)`` slice for length-delimited fields; fixed-width fields
    are skipped."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")


def _str(b: bytes, span: Tuple[int, int]) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _metadata(b: bytes, span, stat_names: Dict[int, str]):
    """(name, tf_op) of one XEventMetadata."""
    name, tf_op = "", None
    for f, v in _fields(b, *span):
        if f == 2:
            name = _str(b, v)
        elif f == 5:
            sid, value = None, None
            for sf, sv in _fields(b, *v):
                if sf == 1:
                    sid = sv
                elif sf == 5:
                    value = _str(b, sv)
                elif sf == 7:
                    value = stat_names.get(sv)
            if stat_names.get(sid) == "tf_op":
                tf_op = value
    return name, tf_op


def _plane(b: bytes, span):
    """One XPlane's name and the events the reduction reads, as
    ``[(line name, [(start_ns, end_ns, (metadata name, tf_op))])]``: on a
    device plane every event of its ``XLA Modules`` and ``XLA Ops`` lines,
    on a host plane the ``bench.*`` and ``repro.*`` spans."""
    name, lines, meta_spans, stat_names = "", [], {}, {}
    for f, v in _fields(b, *span):
        if f == 2:
            name = _str(b, v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            key, value = None, None
            for ef, ev in _fields(b, *v):
                if ef == 1:
                    key = ev
                elif ef == 2:
                    value = ev
            if f == 4:
                meta_spans[key] = value
            else:
                stat_names[key] = next(
                    (_str(b, sv) for sf, sv in _fields(b, *value)
                     if sf == 2), "")
    meta = {k: _metadata(b, v, stat_names) for k, v in meta_spans.items()}
    device = name.startswith("/device:")
    wanted = {k for k, (n, _) in meta.items() if n.startswith(HOST_PREFIXES)}
    out = []
    for span_l in lines:
        lname, ts_ns, events = "", 0, []
        for f, v in _fields(b, *span_l):
            if f == 2:
                lname = _str(b, v)
            elif f == 3:
                ts_ns = v
            elif f == 4:
                events.append(v)
        if device and lname not in DEVICE_LINES:
            continue
        evs = []
        for start, stop in events:
            if not device:
                # a host plane holds every traced call: look at the
                # metadata id (field 1, serialized first) before the rest
                key, i = _varint(b, start)
                if key != 8 or _varint(b, i)[0] not in wanted:
                    continue
            mid = off = dur = 0
            for f, v in _fields(b, start, stop):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            # whole nanoseconds, as jax.profiler.ProfileData has them
            a = ts_ns + off // 1000
            evs.append((a, a + dur // 1000, meta.get(mid, ("", None))))
        out.append((lname, evs))
    return name, out


def read_xspace(path: str) -> List[tuple]:
    """Every plane of the trace at ``path`` (``.xplane.pb``, or gzipped)
    as ``(name, lines)``, with the events :func:`_plane` keeps."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        b = f.read()
    return [_plane(b, v) for f, v in _fields(b, 0, len(b)) if f == 1]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def scope_of(tf_op: Optional[str]) -> str:
    """The first named scope that encloses an op on its ``tf_op`` path
    (``jit(f)/scope/.../op:type``), or ``unscoped``.  The last name on the
    path is the op's own, not a scope (an XLA ``gather`` is no ``gather``
    scope)."""
    if tf_op:
        for part in tf_op.rsplit(":", 1)[0].split("/")[:-1]:
            if part in SCOPES:
                return part
    return UNSCOPED


def _stretches(ops):
    """``(start, end, op)`` for each stretch of time of the innermost op
    running then; ``ops`` are ``(start, end, op)``."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    points = sorted({t for a, b, _ in ops for t in (a, b)})
    active: list = []
    j = 0
    for x0, x1 in zip(points, points[1:]):
        while j < len(ops) and ops[j][0] <= x0:
            active.append(ops[j])
            j += 1
        active = [o for o in active if o[1] > x0]
        if active:
            yield x0, x1, active[-1][2]


def reduce_scopes(path: str, chips: int) -> dict:
    """The trace at ``path`` reduced over its ``bench.window`` span and the
    cell's first ``chips`` chips: ``window_s``, ``busy_s``, ``devices``,
    ``programs_s`` and ``gaps`` as ``bench/xplane.py`` computes them
    (gaps named by ``repro.*`` spans too), and ``scopes_s`` and
    ``program_scopes_s`` (program -> scope -> seconds), averaged over the
    chips."""
    host: List[Tuple[str, int, int]] = []
    devices = []
    for name, lines in read_xspace(path):
        if name.startswith("/device:TPU"):
            devices.append((name, lines))
        elif name.startswith("/host"):
            host += [(m[0], a, b) for _, evs in lines for a, b, m in evs]
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW} span")
    lo, hi = windows[0]
    devices = sorted(devices, key=lambda p: p[0])[:chips]
    n = max(len(devices), 1)
    busy_ns, programs = [], {}
    scopes: Dict[str, float] = {}
    prog_scopes: Dict[str, Dict[str, float]] = {}
    first_gaps: List[Tuple[int, int]] = []
    for i, (_, lines) in enumerate(devices):
        ops, mods = [], []
        for lname, evs in lines:
            for a, b, (ename, tf_op) in evs:
                a, b = max(a, lo), min(b, hi)
                if b <= a:
                    continue
                if lname == "XLA Modules":
                    mods.append((a, b, ename.split("(")[0]))
                elif lname == "XLA Ops":
                    ops.append((a, b, scope_of(tf_op)))
        for a, b, prog in mods:
            programs[prog] = programs.get(prog, 0) + (b - a)
        busy = _union([(a, b) for a, b, _ in ops])
        busy_ns.append(sum(b - a for a, b in busy))
        if i == 0:
            first_gaps = _gaps(busy, lo, hi)
        # a chip runs one program at a time: each stretch of op time
        # belongs to the program running then
        mods.sort()
        k = 0
        for x0, x1, scope in _stretches(ops):
            s = (x1 - x0) / 1e9 / n
            scopes[scope] = scopes.get(scope, 0.0) + s
            while k < len(mods) and mods[k][1] <= x0:
                k += 1
            if k < len(mods) and mods[k][0] <= x0:
                d = prog_scopes.setdefault(mods[k][2], {})
                d[scope] = d.get(scope, 0.0) + s
    gaps = [(name_gap(host, a, b), (b - a) / 1e9) for a, b in
            sorted(first_gaps, key=lambda g: g[0] - g[1])[:GAPS]]
    return dict(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_ns) / n / 1e9,
        devices=len(devices),
        programs_s={k: v / n / 1e9 for k, v in programs.items()},
        scopes_s=scopes,
        program_scopes_s=prog_scopes,
        gaps=gaps)


def name_gap(host: List[Tuple[str, int, int]], a: int, b: int) -> str:
    """The host span (other than the window) that overlaps ``[a, b)``
    most; of equal overlaps the shorter one, the inner span."""
    best, name = (0, 0), "host idle"
    for hname, ha, hb in host:
        ov = min(b, hb) - max(a, ha)
        if hname != WINDOW and ov > 0 and (ov, ha - hb) > best:
            best, name = (ov, ha - hb), hname
    return name


# ---------------------------------------------------------------------------
# per-batch readings of one run: ``red`` is reduce_scopes' result, ``spans``
# what repro.tracing.drain() handed over after the window, [t0, t1] the
# window on the host's monotonic clock
# ---------------------------------------------------------------------------

def _begun(spans, name: str, t0: float, t1: float) -> list:
    return [s for s in spans if s.name == name and t0 <= s.t0 <= t1]


def scope_ms_per_run(red: Optional[dict], spans, t0: float, t1: float,
                     scope: str) -> Optional[float]:
    """Device time of ``scope`` in the window over the ``repro.session.run``
    spans begun in it, in ms: what one batch spends in that stage."""
    runs = _begun(spans, "repro.session.run", t0, t1)
    if red is None or not runs or not red.get("scopes_s"):
        return None
    return 1e3 * red["scopes_s"].get(scope, 0.0) / len(runs)


def host_batch_ms(spans, t0: float, t1: float) -> Optional[float]:
    """Mean over the ``repro.serve.batch`` spans begun in the window of
    their length less that of the ``repro.session.device`` spans of the
    same batch, in ms: the host's part of a served batch."""
    batches = _begun(spans, "repro.serve.batch", t0, t1)
    if not batches:
        return None
    device: Dict[int, float] = {}
    for s in spans:
        if s.name == "repro.session.device":
            device[s.batch_id] = device.get(s.batch_id, 0.0) + s.t1 - s.t0
    return 1e3 * sum(b.t1 - b.t0 - device.get(b.span_id, 0.0)
                     for b in batches) / len(batches)
