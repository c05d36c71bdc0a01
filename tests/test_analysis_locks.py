"""repro.analysis.locks: static acquisition-graph extraction + the
runtime-instrumented mode (DESIGN.md Sec. 10.3).

The self-tests the ISSUE requires: an injected lock inversion must be
caught BOTH statically (a doctored module fed to the extractor) and at
runtime (wrong-order acquisition on instrumented locks), while the real
repo stays clean in both modes.
"""
import threading

import numpy as np

from repro.analysis import (LOCK_ORDER, InstrumentedLock, LockMonitor,
                            check_lock_order, monitored)
from repro.analysis.locks import check_edges, extract_acquisition_graph
from repro.graph import erdos_renyi, random_partition


# --- static mode -----------------------------------------------------------

def test_repo_acquisition_graph_respects_declared_order():
    import os
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    vs, edges = check_lock_order(root)
    assert [str(v) for v in vs] == []
    # the extraction is not vacuous: the known hot edges are present
    assert ("store._repair_lock", "session._lock") in edges
    assert ("store._repair_lock", "store._lock") in edges
    assert ("engine._mutex", "telemetry._lock") in edges


def _doctored(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(body)
    return str(p)


def test_injected_static_inversion_caught(tmp_path):
    """store._lock held while taking store._repair_lock inverts the
    declared order and must be rejected."""
    bad = (
        "class VersionedCacheStore:\n"
        "    def commit_delta(self, delta):\n"
        "        with self._lock:\n"
        "            with self._repair_lock:\n"
        "                pass\n"
    )
    vs, edges = check_lock_order(
        files={_doctored(tmp_path, "versions.py", bad): "store"})
    assert ("store._lock", "store._repair_lock") in edges
    assert [v.rule for v in vs] == ["LCK001"]
    assert "store._lock -> store._repair_lock" in vs[0].where


def test_injected_inversion_through_cross_module_call_caught(tmp_path):
    """The inversion only exists interprocedurally: telemetry holds its
    lock and calls back into the session, which takes session._lock."""
    tele = (
        "class Telemetry:\n"
        "    def record(self, sess):\n"
        "        with self._lock:\n"
        "            self.session.snapshot()\n"
    )
    sess = (
        "class QuerySession:\n"
        "    def snapshot(self):\n"
        "        with self._lock:\n"
        "            return 1\n"
    )
    vs, edges = check_lock_order(files={
        _doctored(tmp_path, "telemetry.py", tele): "telemetry",
        _doctored(tmp_path, "session.py", sess): "session",
    })
    assert ("telemetry._lock", "session._lock") in edges
    assert [v.rule for v in vs] == ["LCK001"]


def test_static_self_deadlock_on_plain_lock(tmp_path):
    bad = (
        "class Telemetry:\n"
        "    def snapshot(self):\n"
        "        with self._lock:\n"
        "            with self._lock:\n"
        "                pass\n"
    )
    vs, _ = check_lock_order(
        files={_doctored(tmp_path, "telemetry.py", bad): "telemetry"})
    assert [v.rule for v in vs] == ["LCK002"]


def test_static_reentrant_self_edge_allowed(tmp_path):
    ok = (
        "class QuerySession:\n"
        "    def run(self):\n"
        "        with self._lock:\n"
        "            self._plan()\n"
        "    def _plan(self):\n"
        "        with self._lock:\n"
        "            pass\n"
    )
    vs, edges = check_lock_order(
        files={_doctored(tmp_path, "session.py", ok): "session"})
    assert ("session._lock", "session._lock") in edges
    assert vs == []


def test_static_undeclared_lock_reported(tmp_path):
    bad = (
        "class QuerySession:\n"
        "    def run(self):\n"
        "        with self._lock:\n"
        "            with self._shadow_lock:\n"
        "                pass\n"
    )
    vs, _ = check_lock_order(
        files={_doctored(tmp_path, "session.py", bad): "session"})
    assert [v.rule for v in vs] == ["LCK003"]
    assert "session._shadow_lock" in vs[0].message


def test_condition_objects_alias_the_engine_mutex(tmp_path):
    """with self._work: ... in engine code is an engine._mutex
    acquisition — the Condition wraps it."""
    eng = (
        "class AsyncQueryEngine:\n"
        "    def _next_work(self):\n"
        "        with self._work:\n"
        "            self.telemetry.record(1)\n"
    )
    tele = (
        "class Telemetry:\n"
        "    def record(self, x):\n"
        "        with self._lock:\n"
        "            pass\n"
    )
    edges = extract_acquisition_graph({
        _doctored(tmp_path, "engine.py", eng): "engine",
        _doctored(tmp_path, "telemetry.py", tele): "telemetry",
    })
    assert ("engine._mutex", "telemetry._lock") in edges
    assert check_edges(edges) == []


# --- runtime mode ----------------------------------------------------------

def _locks(monitor):
    return (InstrumentedLock(threading.RLock(), "engine._mutex", monitor),
            InstrumentedLock(threading.Lock(), "telemetry._lock", monitor))


def test_runtime_ordered_acquisition_clean():
    mon = LockMonitor()
    mutex, tlock = _locks(mon)
    with mutex:
        with tlock:
            pass
    assert mon.violations == []


def test_runtime_inversion_caught():
    mon = LockMonitor()
    mutex, tlock = _locks(mon)
    with tlock:
        with mutex:
            pass
    assert [v.rule for v in mon.violations] == ["LCK001"]
    assert "engine._mutex acquired while holding telemetry._lock" in \
        mon.violations[0].message


def test_runtime_inversion_across_threads_is_per_thread():
    """Each thread's stack is independent: thread A holding telemetry
    does not poison thread B's ordered acquisition."""
    mon = LockMonitor()
    mutex, tlock = _locks(mon)
    hold = threading.Event()
    done = threading.Event()

    def holder():
        with tlock:
            hold.set()
            done.wait(timeout=5)

    th = threading.Thread(target=holder)
    th.start()
    hold.wait(timeout=5)
    with mutex:                    # ordered for THIS thread
        pass
    done.set()
    th.join()
    assert mon.violations == []


def test_runtime_nonreentrant_double_acquire_flagged():
    mon = LockMonitor()
    # RLock inner so the test does not actually deadlock; the NAME
    # store._lock is declared non-reentrant
    lk = InstrumentedLock(threading.RLock(), "store._lock", mon)
    with lk:
        with lk:
            pass
    assert [v.rule for v in mon.violations] == ["LCK002"]


def test_runtime_undeclared_lock_flagged():
    mon = LockMonitor()
    lk = InstrumentedLock(threading.Lock(), "mystery._lock", mon)
    with lk:
        pass
    assert [v.rule for v in mon.violations] == ["LCK003"]


def test_condition_over_instrumented_rlock_keeps_stack_consistent():
    """Condition.wait releases ALL recursion levels through
    _release_save; the monitor must drop the name so the reacquisition
    after notify is not a false inversion."""
    mon = LockMonitor()
    mutex, tlock = _locks(mon)
    cond = threading.Condition(mutex)
    woke = []

    def waiter():
        with cond:
            cond.wait(timeout=5)
            with tlock:            # ordered acquisition after wakeup
                woke.append(1)

    th = threading.Thread(target=waiter)
    th.start()
    import time
    time.sleep(0.1)
    with cond:
        cond.notify_all()
    th.join()
    assert woke == [1]
    assert mon.violations == []


def test_monitored_serving_stack_end_to_end():
    """A real QueryServer built under monitored() runs every dispatch,
    flush, and telemetry read on instrumented locks — and stays clean."""
    from repro.core import fragment_graph
    from repro.serve import QueryServer

    g = erdos_renyi(14, 26, n_labels=3, seed=3)
    fr = fragment_graph(g, random_partition(g, 2, 3), 2)
    with monitored() as mon:
        srv = QueryServer(fr, batch_size=4, start=False)
        assert isinstance(srv.engine._mutex, InstrumentedLock)
        rng = np.random.default_rng(0)
        reqs = [srv.submit(int(rng.integers(g.n)), int(rng.integers(g.n)))
                for _ in range(6)]
        srv.flush()
        vals = [r.value for r in reqs]
        srv.telemetry()
        srv.close()
    assert all(v in (True, False) for v in vals)
    assert [str(v) for v in mon.violations] == []


def test_lock_order_is_total_and_matches_design():
    assert list(LOCK_ORDER) == [
        "engine._serve_mutex", "engine._mutex", "store._repair_lock",
        "session._lock", "store._lock", "telemetry._lock", "tracing._lock"]
