"""Host spans at the layer boundaries of the served query path
(DESIGN.md Sec. 8.4).

``span(name, **attrs)`` marks one layer's work: the engine's batch, the
session's plan and groups, a group's input gathers and its device call.
Every span is a ``jax.profiler.TraceAnnotation``, so a profiler trace
carries it on the clock of the device's operations; with no profiler
running that costs next to nothing.

Recording is off by default.  ``enable()`` also keeps every finished span
in a bounded ring in host memory, on ``time.monotonic``, until ``drain()``
hands them over.  A span opened inside another on the same thread is its
child (``parent_id``), and every span under one outermost span carries
that span's id as its ``batch_id``: one served batch, from the engine's
``repro.serve.batch`` down to each group's device call.  Recording is
process-wide, like the profiler it pairs with.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Iterator, List, NamedTuple, Optional, Tuple

import jax

CAPACITY = 1 << 16      # spans kept before the oldest are dropped


class Span(NamedTuple):
    name: str
    t0: float               # time.monotonic at entry
    t1: float               # ... and at exit
    span_id: int
    parent_id: Optional[int]
    batch_id: int           # span_id of the outermost span around it
    attrs: dict


class _Ring:
    """The newest ``capacity`` finished spans and how many older ones were
    dropped.  ``_lock`` is a leaf: nothing is acquired while it is held."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._dropped = 0

    def add(self, sp: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._dropped += 1
            self._spans.append(sp)

    def take(self) -> Tuple[List[Span], int]:
        with self._lock:
            out, dropped = list(self._spans), self._dropped
            self._spans.clear()
            self._dropped = 0
        return out, dropped


_ring: Optional[_Ring] = None
_ids = itertools.count(1)
_open = threading.local()       # per thread: stack of (span_id, batch_id)


def enable(capacity: int = CAPACITY) -> None:
    """Start recording into a new, empty ring of ``capacity`` spans."""
    global _ring
    _ring = _Ring(capacity)


def disable() -> None:
    """Stop recording; spans not yet drained are discarded."""
    global _ring
    _ring = None


def drain() -> Tuple[List[Span], int]:
    """The spans recorded since the last drain, oldest first, and how many
    the ring dropped in that time (``([], 0)`` while recording is off)."""
    ring = _ring
    return ([], 0) if ring is None else ring.take()


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[None]:
    """Mark the enclosed work as ``name``; ``attrs`` go with it into the
    profiler trace and the ring."""
    with jax.profiler.TraceAnnotation(name, **attrs):
        ring = _ring
        if ring is None:
            yield
            return
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        span_id = next(_ids)
        parent_id, batch_id = stack[-1] if stack else (None, span_id)
        stack.append((span_id, batch_id))
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            stack.pop()
            ring.add(Span(name, t0, t1, span_id, parent_id, batch_id,
                          attrs))
