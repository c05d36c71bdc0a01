"""Continuous-batching async serving: futures, fencing, telemetry
(DESIGN.md Sec. 8).

The contracts under test, all with the scheduler thread *running* (the
deferred ``start=False`` mode is covered throughout the chaos/session/
incremental suites):

* concurrent submitters get oracle-exact answers, and every future
  resolves exactly once;
* a mid-stream delta is a snapshot barrier on both backends — pre-delta
  futures answer against the pre-delta cache (witnessed by the stamped
  ``cache_version``), post-delta futures against the repaired one;
* deadlines and poison requests resolve typed (``DEADLINE`` /
  ``DEAD_LETTER``) without wedging the scheduler;
* the deprecated ``drain()`` warns and still returns the PR-7 shape;
* telemetry aggregates what actually happened.
"""
import threading

import numpy as np
import pytest

from repro import connect
from repro.core import GraphDelta, fragment_graph
from repro.errors import (DeadLetterError, DeadlineExceeded, InjectedFault,
                          Status)
from repro.graph import erdos_renyi, random_partition
from repro.serve import FaultInjector, QueryServer, RetryPolicy

from oracles import oracle_dist, oracle_reach

RESULT_TIMEOUT_S = 120.0      # generous: first result may pay the compiles


def _case(n, m, k, seed, **kw):
    g = erdos_renyi(n, m, n_labels=3, seed=seed)
    fr = fragment_graph(g, random_partition(g, k, seed), k, **kw)
    return g, fr


def _unreachable_pair(g, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(500):
        s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
        if s != t and not oracle_reach(g, s, t):
            return s, t
    pytest.skip("graph is (almost) strongly connected")


# ---------------------------------------------------------------------------
# concurrent submitters: oracle-exact, exactly-once
# ---------------------------------------------------------------------------

def test_concurrent_submitters_oracle_exact_exactly_once():
    g, fr = _case(30, 90, 3, seed=5)
    n_workers, per_worker = 4, 12
    failures = []

    def worker(wid, srv):
        rng = np.random.default_rng(wid)
        futs = []
        for i in range(per_worker):
            s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
            kind = "dist" if i % 2 else "reach"
            futs.append((s, t, kind, srv.submit(s, t, kind=kind)))
        for s, t, kind, f in futs:
            got = f.result(timeout=RESULT_TIMEOUT_S)
            want = (oracle_dist(g, s, t) if kind == "dist"
                    else oracle_reach(g, s, t))
            if got != want or f.status is not Status.DONE:
                failures.append((wid, s, t, kind, got, want, f.status))

    with QueryServer(fr, batch_size=8, batch_wait_ms=1.0) as srv:
        threads = [threading.Thread(target=worker, args=(w, srv))
                   for w in range(n_workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=RESULT_TIMEOUT_S)
        assert not any(th.is_alive() for th in threads)
        assert failures == []
        # exactly-once: every submission reached exactly one terminal
        # status (the engine asserts no future resolves twice)
        snap = srv.telemetry()
        total = n_workers * per_worker
        assert snap["resolved"] == total
        assert snap["statuses"] == {"done": total}
        assert srv.pending() == 0


def test_two_servers_share_one_session():
    """Multiple intake frontends over ONE session: the session lock
    serializes group execution, both serve oracle-exact from the shared
    caches."""
    g, fr = _case(24, 70, 2, seed=9)
    sess = connect(fr)
    srv_a = QueryServer(fr, session=sess, batch_size=4, batch_wait_ms=1.0)
    srv_b = QueryServer(fr, session=sess, batch_size=4, batch_wait_ms=1.0)
    try:
        assert srv_a.session is srv_b.session
        rng = np.random.default_rng(2)
        futs = []
        for i in range(20):
            s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
            futs.append((s, t, (srv_a if i % 2 else srv_b).submit(s, t)))
        for s, t, f in futs:
            assert f.result(timeout=RESULT_TIMEOUT_S) == oracle_reach(g, s, t)
    finally:
        srv_a.close()
        srv_b.close()


# ---------------------------------------------------------------------------
# mid-stream deltas are snapshot barriers (both backends)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_mid_stream_delta_fencing(backend):
    g, fr = _case(24, 30, 3, seed=11, reserve_boundary=8, reserve_edges=24,
                  reserve_stubs=12)
    s, t = _unreachable_pair(g)
    with QueryServer(fr, batch_size=4, backend=backend) as srv:
        pre = srv.submit(s, t)
        upd = srv.submit_delta(GraphDelta.insert([(s, t)]))
        post = srv.submit(s, t)
        # pre-delta future answers against the pre-delta snapshot, even
        # though the delta was already queued when it (maybe) executed
        assert pre.result(timeout=RESULT_TIMEOUT_S) is False
        assert upd.result(timeout=RESULT_TIMEOUT_S).mode in (
            "repair", "recompute", "repair_sharded", "rebuild")
        assert upd.status is Status.APPLIED
        assert post.result(timeout=RESULT_TIMEOUT_S) is True
        # the fencing witness: version stamped at execution time
        assert pre.cache_version < post.cache_version
        assert srv.updates_applied == 1


# ---------------------------------------------------------------------------
# deadlines + poison under the async scheduler
# ---------------------------------------------------------------------------

def test_deadline_resolves_typed_without_wedging_the_scheduler():
    g, fr = _case(20, 50, 2, seed=3)
    # batch_wait is huge: only deadline pressure can ship a partial bucket
    with QueryServer(fr, batch_size=64, batch_wait_ms=60_000.0,
                     ship_margin_ms=25.0) as srv:
        dead = srv.submit(0, 1, deadline_ms=0.0)       # already expired
        with pytest.raises(DeadlineExceeded):
            dead.result(timeout=RESULT_TIMEOUT_S)
        assert dead.status is Status.DEADLINE
        # a generous deadline ships the bucket well before expiring
        live = srv.submit(0, 1, deadline_ms=30_000.0)
        assert live.result(timeout=RESULT_TIMEOUT_S) == oracle_reach(g, 0, 1)
        assert live.status is Status.DONE


def test_poison_dead_letters_async_and_batchmates_survive():
    g, fr = _case(20, 50, 2, seed=7)
    chaos = FaultInjector(seed=0, poison=[(0, 1)])
    srv = QueryServer(fr, batch_size=8, backend="vmap", chaos=chaos,
                      batch_wait_ms=200.0,
                      retry=RetryPolicy(max_attempts=2, base_delay_ms=0.0))
    try:
        # same bucket: the poison request and innocent batchmates
        mates = [srv.submit(i, (i + 3) % g.n) for i in range(2, 6)]
        poison = srv.submit(0, 1)
        for f in mates:
            assert (f.result(timeout=RESULT_TIMEOUT_S)
                    == oracle_reach(g, f.s, f.t))
        with pytest.raises(DeadLetterError) as ei:
            poison.result(timeout=RESULT_TIMEOUT_S)
        assert poison.status is Status.DEAD_LETTER
        assert isinstance(ei.value.cause, InjectedFault)
        assert ei.value.cause.permanent
        assert srv.dead_letters == [poison]
        # scheduler is still alive and serving after the quarantine
        again = srv.submit(2, 5)
        assert again.result(timeout=RESULT_TIMEOUT_S) == oracle_reach(g, 2, 5)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# drain() compatibility + telemetry
# ---------------------------------------------------------------------------

def test_drain_compat_warns_and_matches_futures_path():
    g, fr = _case(22, 60, 2, seed=1)
    rng = np.random.default_rng(4)
    pairs = [(int(rng.integers(g.n)), int(rng.integers(g.n)))
             for _ in range(9)]
    # legacy path: deferred server + deprecated drain()
    old = QueryServer(fr, batch_size=4, start=False)
    legacy = [old.submit(s, t) for s, t in pairs]
    with pytest.warns(DeprecationWarning, match="drain.*deprecated"):
        served = old.drain()
    assert sorted(map(id, served)) == sorted(map(id, legacy))
    # new path: continuous server + futures
    with QueryServer(fr, batch_size=4) as srv:
        fresh = [srv.submit(s, t) for s, t in pairs]
        for (s, t), a, b in zip(pairs, legacy, fresh):
            want = oracle_reach(g, s, t)
            assert a.value == b.result(timeout=RESULT_TIMEOUT_S) == want


def test_telemetry_reflects_served_load():
    g, fr = _case(24, 70, 2, seed=6)
    with QueryServer(fr, batch_size=4, batch_wait_ms=1.0) as srv:
        futs = [srv.submit(i % g.n, (i * 7) % g.n,
                           kind="dist" if i % 3 == 0 else "reach")
                for i in range(12)]
        for f in futs:
            f.result(timeout=RESULT_TIMEOUT_S)
        snap = srv.telemetry()
    assert snap["resolved"] == 12
    assert snap["statuses"] == {"done": 12}
    assert snap["batches"] == srv.batches_run >= 3     # 12 queries, bucket 4
    assert 0.0 < snap["batch_occupancy"] <= 1.0
    assert snap["qps"] > 0.0
    assert set(snap["lane_depths"]) == {"green", "yellow", "updates"}
    assert all(v == 0 for v in snap["lane_depths"].values())
    routes = snap["routes"]
    assert sum(r["count"] for r in routes.values()) == 12
    for r in routes.values():
        assert 0.0 <= r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]


def test_telemetry_routes_cover_each_served_kind():
    """Latency is kept per route (``kind/lane``): a mixed reach + dist load
    shows up under at least one route of each kind, each counting exactly
    the queries of its kind."""
    g, fr = _case(24, 70, 2, seed=8)
    kinds = ["dist" if i % 2 else "reach" for i in range(10)]
    with QueryServer(fr, batch_size=4, batch_wait_ms=1.0) as srv:
        futs = [srv.submit(i % g.n, (i * 5) % g.n, kind=kind)
                for i, kind in enumerate(kinds)]
        for f in futs:
            f.result(timeout=RESULT_TIMEOUT_S)
        routes = srv.telemetry()["routes"]
    assert len(routes) >= 2, routes
    per_kind = {}
    for route, r in routes.items():
        kind = route.split("/")[0]
        per_kind[kind] = per_kind.get(kind, 0) + r["count"]
    assert per_kind == {"reach": 5, "dist": 5}, routes


def test_submit_after_close_is_refused():
    g, fr = _case(10, 20, 2, seed=0)
    srv = QueryServer(fr, batch_size=4, warm=False, start=False)
    srv.close()
    with pytest.raises(RuntimeError, match="stopped"):
        srv.submit(0, 1)
