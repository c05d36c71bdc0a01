"""Builds the system under test for a cell, warms it up and drives its
window; collects what the window produced for the comparison and the
metric readers.

The system is reached only through its public serving path:
``repro.connect`` -> ``QueryServer`` in continuous mode, requests through
``submit`` and answers through their futures.  Two wrappers on the live
objects record what happens inside: a host span around ``QuerySession.run``
(``jax.profiler.TraceAnnotation`` too, so a device trace can say what the
host was doing in each idle gap), and the engine telemetry's
``record_batch`` counter.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

from . import workload as wl

SPAN_RUN = "bench.session_run"
SPAN_SUBMIT = "bench.submit"
SPAN_WINDOW = "bench.window"
POLL_S = 0.002          # closed loop: longest a finished read waits to be replaced
CLOSE_S = 0.05          # time a batch's answers take to resolve, at most


@dataclasses.dataclass
class Record:
    """What the instrumentation saw, on the host's monotonic clock."""

    spans: List[tuple] = dataclasses.field(default_factory=list)   # (name, t0, t1)
    batches: List[tuple] = dataclasses.field(default_factory=list)  # (t, chunk, size)
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def span(self, name: str, t0: float, t1: float) -> None:
        with self.lock:
            self.spans.append((name, t0, t1))


def _traced(rec: Record, name: str, fn):
    import jax

    def wrapper(*args, **kwargs):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*args, **kwargs)
        rec.span(name, t0, time.monotonic())
        return out
    return wrapper


def instrument(session, server, rec: Record) -> None:
    session.run = _traced(rec, SPAN_RUN, session.run)
    tel = server.engine.telemetry
    record_batch = tel.record_batch

    def counted(chunk_size, batch_size):
        with rec.lock:
            rec.batches.append((time.monotonic(), int(chunk_size),
                                int(batch_size)))
        record_batch(chunk_size, batch_size)
    tel.record_batch = counted


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class System:
    fr: object
    session: object
    server: object


def build(cfg: dict, g: wl.Graph) -> System:
    import repro
    from repro.core import fragment_graph
    from repro.graph import Graph
    from repro.serve import QueryServer, RetryPolicy

    caps = cfg["capacities"]
    res = wl.reserves_for(g, caps, cfg["pad_multiple"])
    fr = fragment_graph(Graph(g.n, g.src, g.dst, g.labels), g.part, g.k,
                        pad_multiple=cfg["pad_multiple"], **res)
    got = dict(nb=fr.n_boundary, n_max=fr.n_max, e_max=fr.e_max,
               s_max=fr.s_max)
    if any(got[k] != caps[k] for k in got):
        raise RuntimeError(f"fragment capacities {got} differ from the "
                           f"configuration's {caps}")
    session = repro.connect(fr, backend=cfg["backend"])
    session.warm(with_dist=cfg["warm_with_dist"])
    srv = cfg["server"]
    # one attempt per batch: a batch that raises is a failure to count,
    # not one to hide behind a retry
    server = QueryServer(fr, session=session, warm=False,
                         with_dist=cfg["warm_with_dist"],
                         batch_size=srv["batch_size"],
                         batch_wait_ms=srv["batch_wait_ms"],
                         mvcc=srv["mvcc"], versions=srv["versions"],
                         retry=RetryPolicy(max_attempts=1), start=False)
    return System(fr, session, server)


def _query(read: wl.Read, regex: str):
    from repro.core.plan import Dist, Reach, Rpq
    if read.kind == "reach":
        return Reach(read.s, read.t)
    if read.kind == "rpq":
        return Rpq(read.s, read.t, regex=regex)
    return Dist(read.s, read.t, bound=read.bound)


def warm_up(system: System, cfg: dict, tr: dict, g: wl.Graph,
            seed: int) -> dict:
    """Compile and load every program the window can run, outside it.

    The scheduler's chunks are ragged (it ships a partial chunk after
    ``batch_wait_ms``, and admission puts cheap and costly reads in
    separate lanes), so every kind of group runs once at every bucket size
    a chunk of ``batch_size`` can make."""
    from repro.core.plan import bucket_size
    session, bs = system.session, cfg["server"]["batch_size"]
    sizes = sorted({bucket_size(n) for n in range(1, bs + 1)})
    pool = wl.warm_reads(g, tr, seed, 4 * bs * len(tr["kinds"]))
    groups = {"bounded": "dist"}      # bounded and exact dist fuse
    for kind in sorted({groups.get(k, k) for k in tr["kinds"]}):
        mine = [r for r in pool if groups.get(r.kind, r.kind) == kind]
        for n in sizes:
            session.run([_query(r, tr["regex"]) for r in mine[:n]])
    return dict(warm_buckets=sizes)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    """One read as the client saw it."""

    req: wl.Read
    start: float                # when submit was called
    fut: object


def _submit(server, req: wl.Read, regex: str):
    if req.kind == "rpq":
        return server.submit(req.s, req.t, kind="rpq", regex=regex)
    return server.submit(req.s, req.t, kind=req.kind, bound=req.bound)


def run_closed(system: System, maker: wl.ReadMaker, outstanding: int,
               seconds: float, regex: str, rec: Record) -> tuple:
    """Keep ``outstanding`` reads in flight: each answer lets the client
    send the next read.  The queue is filled before the scheduler starts,
    so the window begins with a full backlog.  Answers come back out of
    order (admission lanes), so the client looks at every read in flight
    each time it wakes, at most ``POLL_S`` apart.

    The window opens when the scheduler starts and closes once the first
    batch that completes ``seconds`` or later after that has handed back
    all its answers (see ``close_window``): the reads answered are then
    whole batches, and their count over the window's length carries no
    rounding to a batch."""
    import jax
    server = system.server
    sent: List[Sent] = []

    def send() -> Sent:
        req = maker.next()
        a = time.monotonic()
        fut = _submit(server, req, regex)
        rec.span(SPAN_SUBMIT, a, time.monotonic())
        s = Sent(req, a, fut)
        sent.append(s)
        return s

    live = [send() for _ in range(outstanding)]
    server.engine.start()
    t0 = time.monotonic()
    end = t0 + seconds
    with jax.profiler.TraceAnnotation(SPAN_WINDOW):
        while time.monotonic() < end:
            settle(live[0].fut, min(POLL_S, end - time.monotonic()))
            live = [s if not s.fut.done() or time.monotonic() >= end
                    else send() for s in live]
        t1 = close_window(rec, sent, end)
    return t0, t1, sent


def close_window(rec: Record, sent: List[Sent], end: float,
                 wait_s: float = 120.0) -> float:
    """The moment the first batch completed at or after ``end`` resolved
    its last future.  The engine serves one batch at a time and resolves
    a batch's futures right after its ``record_batch``, before it forms
    the next one; so that batch's futures are those resolved from its
    record up to the next batch's record (or up to now, once some time
    has passed with no further record).  Returns ``end`` if no batch
    completes within ``wait_s``."""
    give_up = time.monotonic() + wait_s
    while True:
        with rec.lock:
            after = [t for t, _, _ in rec.batches if t >= end]
        if after or time.monotonic() >= give_up:
            break
        time.sleep(POLL_S)
    if not after:
        return end
    tb = after[0]
    time.sleep(CLOSE_S)
    with rec.lock:
        nxt = [t for t, _, _ in rec.batches if t > tb]
    until = nxt[0] if nxt else float("inf")
    resolved = [s.fut.resolved_at for s in sent if s.fut.done()
                and tb <= s.fut.resolved_at < until]
    return max(resolved, default=tb)


def settle(fut, timeout: float) -> bool:
    """Wait up to ``timeout`` seconds for ``fut`` to reach a terminal
    status, whichever it is; True once it has."""
    from repro.errors import ServingError
    try:
        fut.result(timeout=max(timeout, 0.0))
    except TimeoutError:
        return False
    except ServingError:
        pass            # a failed request: counted from its status
    return True


def wait_all(sent: List[Sent], until: float) -> int:
    """Wait for every request to resolve, at most until ``until``; returns
    how many never did."""
    return sum(not settle(s.fut, until - time.monotonic()) for s in sent)


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
