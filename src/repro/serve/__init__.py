"""Serving layer: continuous-batching futures-based query serving over a
shared :class:`~repro.core.session.QuerySession` (DESIGN.md Secs. 7–8).

Public surface (everything here is re-exported at this level):

* :class:`QueryServer` — intake + lifecycle: validates/admits requests,
  returns futures, owns the scheduler thread (``start=True``) or the
  deterministic deferred mode (``start=False`` + ``flush()``).
* :class:`AsyncQueryEngine` — the continuous-batching scheduler itself:
  segments fenced by delta barriers, GREEN-before-YELLOW lanes, partial
  buckets shipped on deadline pressure or ``batch_wait`` expiry, PR-7
  retry/bisect/dead-letter execution.
* :class:`QueryFuture` / :class:`UpdateFuture` — awaitable handles
  (``.result(timeout=)``, ``.done()``, ``.status``, non-blocking
  ``.value``).  ``QueryRequest`` / ``UpdateRequest`` are their PR-7
  names, kept as aliases.
* :class:`Status` — the one lifecycle enum (str-valued: ``"done"``,
  ``"dead_letter"``, ``"deadline"``, ``"applied"``, ``"failed"``, ...)
  shared with session results and the error taxonomy.
* :class:`RetryPolicy` — capped exponential backoff for transient
  serving failures.
* :class:`Version` / :class:`VersionedCacheStore` — the MVCC snapshot
  store behind ``QueryServer(..., mvcc=True)``: immutable copy-on-write
  versions, pinned readers, concurrent repair, rollback-as-drop
  (:mod:`repro.core.versions`, DESIGN.md Sec. 9).
* :class:`Telemetry` — sliding-window p50/p95/p99 per route, qps, batch
  occupancy, lane depths (``QueryServer.telemetry()`` snapshots it).
* :class:`AdmissionPolicy` / :func:`estimate_cost` and the lane
  constants ``GREEN`` / ``YELLOW`` / ``RED`` / ``LANES`` — cost-based
  admission control.
* :class:`FaultInjector` / :class:`FaultSpec` / ``SITES`` — seeded fault
  injection for chaos tests.
* the typed error taxonomy (:class:`ServingError` and subclasses).
* :class:`Request` / :class:`ServeEngine` — the unrelated toy LM decode
  loop (:mod:`repro.serve.lm`), kept at its historical import path.
"""
from ..core.versions import Version, VersionedCacheStore
from ..errors import (DeadLetterError, DeadlineExceeded, DeltaApplyFailed,
                      InjectedFault, QueryTooExpensive, ServingError,
                      Status)
from .admission import (GREEN, LANES, RED, YELLOW, AdmissionPolicy,
                        estimate_cost)
from .engine import (AsyncQueryEngine, QueryFuture, RetryPolicy,
                     UpdateFuture)
from .faults import SITES, FaultInjector, FaultSpec
from .lm import Request, ServeEngine
from .query_server import (QueryRequest, QueryServer, UpdateRequest,
                           VALID_KINDS)
from .telemetry import Telemetry

__all__ = [
    "QueryServer", "AsyncQueryEngine",
    "QueryFuture", "UpdateFuture", "QueryRequest", "UpdateRequest",
    "Status", "RetryPolicy", "Telemetry", "VALID_KINDS",
    "Version", "VersionedCacheStore",
    "AdmissionPolicy", "estimate_cost",
    "GREEN", "YELLOW", "RED", "LANES",
    "FaultInjector", "FaultSpec", "SITES",
    "ServingError", "QueryTooExpensive", "DeadlineExceeded",
    "DeadLetterError", "DeltaApplyFailed", "InjectedFault",
    "Request", "ServeEngine",
]
