"""The served path's own instrumentation (DESIGN.md Sec. 8.4): host spans
in ``repro.tracing`` (off by default; nested and grouped by batch when on;
a bounded ring), the session's row counters, the named scopes of the batch
programs, the sharded programs' names and the Pallas kernels' names."""
import sys
import threading

import jax
import numpy as np
import pytest

from repro import connect, tracing
from repro.core import Dist, Reach, Rpq, fragment_graph
from repro.core import cache as _cache
from repro.core import distributed, incremental
from repro.core.fragments import Placement
from repro.graph import erdos_renyi, random_partition
from repro.serve import QueryServer


@pytest.fixture(scope="module")
def fr():
    g = erdos_renyi(40, 120, n_labels=3, seed=3)
    return fragment_graph(g, random_partition(g, 4, 3), 4)


@pytest.fixture
def recording():
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()


def test_off_by_default_records_nothing(fr):
    assert tracing.drain() == ([], 0)
    connect(fr, backend="vmap").run([Reach(0, 5), Dist(1, 7)])
    assert tracing.drain() == ([], 0)


def test_spans_nest_and_share_a_batch_id_through_the_server(fr, recording):
    srv = QueryServer(fr, batch_size=8, batch_wait_ms=1.0)
    try:
        futs = [srv.submit(s, s + 3, kind=k,
                           bound=4 if k == "bounded" else None)
                for s, k in zip(range(6), ["reach", "dist", "bounded"] * 2)]
        for f in futs:
            f.result(timeout=120)
    finally:
        srv.close()
    spans, dropped = tracing.drain()
    assert dropped == 0
    by_id = {s.span_id: s for s in spans}
    batches = [s for s in spans if s.name == "repro.serve.batch"]
    assert batches and all(b.parent_id is None for b in batches)
    for b in batches:
        mine = [s for s in spans if s.batch_id == b.span_id]
        names = {s.name for s in mine}
        assert {"repro.serve.batch", "repro.serve.resolve",
                "repro.session.run", "repro.session.plan",
                "repro.session.group", "repro.session.inputs",
                "repro.session.device",
                "repro.session.assemble"} <= names, names
        for s in mine:
            if s is b:
                continue
            parent = by_id[s.parent_id]
            # a child lies inside its parent, in time and in the batch
            assert parent.batch_id == b.span_id
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
        want_parent = {"repro.session.run": "repro.serve.batch",
                       "repro.serve.resolve": "repro.serve.batch",
                       "repro.session.plan": "repro.session.run",
                       "repro.session.group": "repro.session.run",
                       "repro.session.inputs": "repro.session.group",
                       "repro.session.device": "repro.session.group",
                       "repro.session.assemble": "repro.session.group"}
        for s in mine:
            if s.name in want_parent:
                assert by_id[s.parent_id].name == want_parent[s.name]
    groups = [s for s in spans if s.name == "repro.session.group"]
    # reach reads ride a dist group once the tropical closure is built
    # (an earlier test's dist read built it), so a chunk that holds a
    # dist read holds no reach group; each group counts its reach reads
    assert "dist" in {g.attrs["kind"] for g in groups} <= {"reach", "dist"}
    assert sum(g.attrs["reach"] for g in groups) == 2
    for g in groups:
        assert g.attrs["bucket"] >= g.attrs["n"] >= g.attrs["reach"]
        # the vmap local stage relaxes min(bucket, k) fragment blocks
        assert g.attrs["frags"] == min(g.attrs["bucket"], fr.k)
        if g.attrs["kind"] == "reach":
            assert g.attrs["reach"] == g.attrs["n"]


def test_spans_of_other_threads_do_not_nest(fr, recording):
    sess = connect(fr, backend="vmap")

    def work():
        sess.run([Reach(0, 3)])

    with tracing.span("outer"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=120)
    assert not t.is_alive()
    spans, _ = tracing.drain()
    outer = next(s for s in spans if s.name == "outer")
    run = next(s for s in spans if s.name == "repro.session.run")
    assert run.parent_id is None and run.batch_id == run.span_id
    assert outer.batch_id != run.batch_id


def test_ring_is_bounded_and_counts_what_it_drops():
    tracing.enable(capacity=4)
    try:
        for i in range(10):
            with tracing.span("s", i=i):
                pass
        spans, dropped = tracing.drain()
        assert [s.attrs["i"] for s in spans] == [6, 7, 8, 9]
        assert dropped == 6
        assert tracing.drain() == ([], 0)
    finally:
        tracing.disable()
    with pytest.raises(ValueError):
        tracing.enable(capacity=0)


def test_ring_counts_every_span_of_concurrent_writers():
    """Threads recording at once lose no span: each is kept or counted as
    dropped, and span ids stay unique."""
    workers, each = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    tracing.enable(capacity=3000)
    try:
        def record(w):
            for i in range(each):
                with tracing.span("w", w=w):
                    with tracing.span("inner"):
                        pass

        threads = [threading.Thread(target=record, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        spans, dropped = tracing.drain()
    finally:
        tracing.disable()
        sys.setswitchinterval(old)
    assert len(spans) == 3000
    assert len(spans) + dropped == 2 * workers * each
    assert len({s.span_id for s in spans}) == len(spans)
    outer = {s.span_id: s for s in spans if s.name == "w"}
    for s in spans:
        if s.name == "inner" and s.parent_id in outer:
            assert s.batch_id == s.parent_id


def test_row_counters_equal_the_planners_buckets(fr):
    # with the tropical closure built the reach reads join the dist group
    sess = connect(fr, backend="vmap").warm(with_dist=True)
    batch = ([Reach(i, i + 9) for i in range(5)]
             + [Dist(i, i + 4) for i in range(3)]
             + [Dist(i, i + 6, bound=3) for i in range(6)]
             + [Rpq(1, 9, regex="0*")])
    before = dict(vars(sess.stats))
    sess.run(batch)
    plan = sess.last_plan
    assert sorted((g.kind, g.n, g.padded_size) for g in plan.groups) == [
        ("dist", 14, 16), ("rpq", 1, 8)]
    assert sess.stats.rows_useful - before["rows_useful"] == len(batch)
    assert sess.stats.rows_padded - before["rows_padded"] == sum(
        g.padded_size for g in plan.groups)
    assert sess.stats.reach_rows - before["reach_rows"] == 5
    assert sess.stats.reach_fused - before["reach_fused"] == 5
    # the dist group's 16 rows relax all 4 fragments once; RPQ none
    assert sess.stats.frag_blocks - before["frag_blocks"] == fr.k == 4


def _kernel_args(fr, kind):
    cache = _cache.prepare_rvset_cache(fr, with_dist=True)
    pairs = np.array([[0, 5], [3, 9]], dtype=np.int64)
    bl, C = ((cache.bl_frontier, cache.closure) if kind == "reach"
             else (cache.bl_dist, cache.dist_closure))
    a = cache.arrays
    return (a["esrc"], a["edst"], a["tgt_local"], bl, C,
            *_cache._batch_inputs(fr, cache, pairs))


@pytest.mark.parametrize("kind", ["reach", "dist"])
def test_batch_programs_carry_the_stage_scopes(fr, kind):
    kernel = {"reach": _cache._batch_reach_kernel,
              "dist": _cache._batch_dist_kernel}[kind]
    text = kernel.lower(*_kernel_args(fr, kind), n_max=fr.n_max).as_text(
        debug_info=True)
    for scope in ("local_stage", "gather", "combine"):
        assert f"jit(_batch_{kind}_kernel)/{scope}/" in text, scope


def test_sharded_programs_have_distinct_names(fr):
    mesh = distributed.fragment_mesh(devices=jax.devices()[:1])
    placement = Placement.balanced(fr, 1)
    _cache.prepare_rvset_cache(fr)
    qa = connect(fr)._resolve_automaton(Rpq(0, 1, regex="0*"))
    pairs = [(0, 5), (3, 9)]
    for kind, name in (("reach", "sharded_reach"), ("dist", "sharded_dist"),
                       ("rpq", "sharded_rpq")):
        hlo = distributed.lower_batch_hlo(fr, pairs, kind, qa=qa, mesh=mesh,
                                          placement=placement)
        assert f"@jit_{name} " in hlo, kind
    row_ids = incremental.pad_row_ids(np.arange(2), pad=4,
                                      cap=fr.n_boundary)
    warm = np.zeros((fr.k, fr.s_max, fr.n_max + 1), dtype=bool)
    hlo = distributed.lower_update_hlo(fr, warm, row_ids, mesh=mesh,
                                       placement=placement)
    assert "@jit_sharded_delta " in hlo
