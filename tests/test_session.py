"""QuerySession / planner / shims vs the seed engine and oracles.

The session is a *routing* layer: whatever the planner fuses, every query
in a mixed reach+dist+RPQ batch must answer exactly like the single-query
seed paths (``dis_*``) and the networkx oracles — under the vmap backend,
the shard_map backend (single-device compat here, 8 fake devices in the
subprocess check), and across ``submit_delta`` snapshot boundaries.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.core import (Dist, GraphDelta, Reach, Rpq, build_query_automaton,
                        dis_dist, dis_reach, dis_rpq, fragment_graph)
from repro.core.plan import bucket_size, plan_queries
from repro.graph import erdos_renyi, random_partition
from repro.serve import QueryServer

from oracles import oracle_dist, oracle_reach, oracle_rpq

REGEXES = ["0* 1*", "(0|1)* 2"]


def _case(n, m, k, seed):
    g = erdos_renyi(n, m, n_labels=3, seed=seed)
    return g, fragment_graph(g, random_partition(g, k, seed), k)


def _automaton(regex):
    return build_query_automaton(regex, lambda x: int(x))


def _draw_mixed(data, n, n_queries):
    """Random mixed-kind batch; a small endpoint pool forces duplicate
    pairs and s == t cases."""
    pool = [(data.draw(st.integers(0, n - 1), label="s"),
             data.draw(st.integers(0, n - 1), label="t"))
            for _ in range(max(2, n_queries // 2))]
    qs = []
    for _ in range(n_queries):
        s, t = pool[data.draw(st.integers(0, len(pool) - 1), label="pair")]
        kind = data.draw(st.integers(0, 2), label="kind")
        if kind == 0:
            qs.append(Reach(s, t))
        elif kind == 1:
            bound = data.draw(st.integers(-1, 4), label="bound")
            qs.append(Dist(s, t, bound=None if bound < 0 else bound))
        else:
            rx = REGEXES[data.draw(st.integers(0, 1), label="rx")]
            qs.append(Rpq(s, t, regex=rx))
    return qs


def _check_against_seed_and_oracle(g, fr, queries, results):
    for q, r in zip(queries, results):
        if isinstance(q, Reach):
            assert r.answer == oracle_reach(g, q.s, q.t), q
            assert r.answer == dis_reach(fr, q.s, q.t).answer
        elif isinstance(q, Dist):
            ref = dis_dist(fr, q.s, q.t, bound=q.bound)
            assert (r.answer, r.distance) == (ref.answer, ref.distance), q
            if q.bound is None:
                assert r.distance == oracle_dist(g, q.s, q.t)
        else:
            qa = q.automaton or _automaton(q.regex)
            assert r.answer == oracle_rpq(g, q.s, q.t, qa), q
            assert r.answer == dis_rpq(fr, q.s, q.t, qa).answer


# ---------------------------------------------------------------------------
# property: mixed batches == seed single-query paths == oracles
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_property_session_mixed_batch_matches_oracles(data):
    n = data.draw(st.integers(4, 20), label="n")
    m = data.draw(st.integers(0, 50), label="m")
    k = data.draw(st.integers(1, 4), label="k")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    g, fr = _case(n, m, k, seed)
    sess = repro.connect(fr, backend="vmap")
    queries = _draw_mixed(data, n, 6)
    results = sess.run(queries)
    assert len(results) == len(queries)
    _check_against_seed_and_oracle(g, fr, queries, results)
    # one fused execution per (kind, automaton) group
    assert sess.stats.executions == sess.last_plan.n_groups


def test_session_shard_map_compat_single_device():
    """backend='shard_map' on a 1-fragment mesh (the only shape a single
    CPU device admits) answers identically to vmap."""
    g = erdos_renyi(14, 35, n_labels=3, seed=4)
    fr = fragment_graph(g, np.zeros(14, np.int32), 1)
    sess = repro.connect(fr, backend="shard_map")
    assert sess.backend == "shard_map"
    qa = _automaton(REGEXES[0])
    queries = [Reach(0, 5), Reach(5, 5), Dist(1, 7), Dist(2, 2, bound=0),
               Rpq(3, 9, automaton=qa), Reach(6, 0)]
    results = sess.run(queries)
    _check_against_seed_and_oracle(g, fr, queries, results)


def test_session_auto_backend_single_device_is_vmap():
    g, fr = _case(12, 30, 3, 0)
    assert repro.connect(fr).backend == "vmap"
    # 3 fragments, 1 device: since the k >> d packing layer, explicit
    # shard_map is valid (all fragments packed onto the one device) and
    # must agree with vmap.
    sess = repro.connect(fr, backend="shard_map")
    assert sess.backend == "shard_map" and sess.placement.d == 1
    queries = [Reach(0, 5), Dist(1, 7), Reach(4, 4)]
    got = [r.answer for r in sess.run(queries)]
    want = [r.answer for r in repro.connect(fr, backend="vmap").run(queries)]
    assert got == want
    with pytest.raises(ValueError, match="backend"):
        repro.connect(fr, backend="nope")
    with pytest.raises(ValueError, match="cache"):
        repro.connect(fr, cache="nope")


# ---------------------------------------------------------------------------
# planner mechanics
# ---------------------------------------------------------------------------

def test_planner_groups_by_kind_and_automaton():
    qa1, qa2 = _automaton(REGEXES[0]), _automaton(REGEXES[1])
    queries = [Reach(0, 1), Dist(0, 1), Rpq(0, 1, automaton=qa1),
               Reach(2, 3), Dist(2, 3, bound=2), Rpq(2, 3, automaton=qa2),
               Rpq(4, 5, automaton=_automaton(REGEXES[0]))]  # equal key
    plan = plan_queries(queries, lambda q: q.automaton)
    assert plan.n_groups == 4          # reach, dist(+bounded), rpq x2
    kinds = [(grp.kind, grp.n) for grp in plan.groups]
    assert kinds == [("reach", 2), ("dist", 2), ("rpq", 2), ("rpq", 1)]
    # submission order is preserved through the group indices
    assert sorted(i for grp in plan.groups for i in grp.indices) == \
        list(range(len(queries)))
    assert "fused executions" in plan.explain()


@pytest.mark.parametrize("reach_in_dist", [False, True])
def test_planner_reach_in_dist_joins_the_dist_group(reach_in_dist):
    qa = _automaton(REGEXES[0])
    queries = [Reach(0, 1), Dist(0, 1), Rpq(0, 1, automaton=qa),
               Reach(2, 3), Dist(2, 3, bound=2), Reach(4, 4)]
    plan = plan_queries(queries, lambda q: q.automaton,
                        reach_in_dist=reach_in_dist)
    got = [(grp.kind, grp.indices, grp.n_reach) for grp in plan.groups]
    if reach_in_dist:
        # one tropical group, in submission order, reach reads included
        assert got == [("dist", [0, 1, 3, 4, 5], 3), ("rpq", [2], 0)]
    else:
        assert got == [("reach", [0, 3, 5], 3), ("dist", [1, 4], 0),
                       ("rpq", [2], 0)]
    # a joined group padded past the two it replaces stays apart: 30
    # reach and 10 dist reads run 32 + 16 rows, not 64
    wide = [Reach(i, i + 1) for i in range(30)] + [Dist(i, 1)
                                                    for i in range(10)]
    plan = plan_queries(wide, lambda q: q.automaton,
                        reach_in_dist=reach_in_dist)
    assert [(grp.kind, grp.padded_size) for grp in plan.groups] == [
        ("reach", 32), ("dist", 16)]
    # a batch without a Dist read keeps its reach group either way
    plan = plan_queries([Reach(0, 1), Rpq(0, 1, automaton=qa)],
                        lambda q: q.automaton, reach_in_dist=reach_in_dist)
    assert [(grp.kind, grp.n) for grp in plan.groups] == [("reach", 1),
                                                           ("rpq", 1)]


def _reach_dist_batch(n, rng):
    """Mixed reach / exact / bounded reads with s == t cases."""
    pairs = [(int(rng.integers(n)), int(rng.integers(n))) for _ in range(14)]
    pairs += [(3, 3), (5, 5)]
    return [[Reach(s, t), Dist(s, t), Dist(s, t, bound=i % 4)][i % 3]
            for i, (s, t) in enumerate(pairs)]


def test_reach_answered_by_the_dist_group_is_exact():
    """On a vmap session whose tropical closure is built, a mixed batch's
    reach reads ride its dist group and answer exactly like an uncached
    session and the oracles; the counters say so, and the group's stats
    still sum to its one collective."""
    g, fr = _case(40, 90, 4, 11)
    sess = repro.connect(fr, backend="vmap").warm(with_dist=True)
    queries = _reach_dist_batch(g.n, np.random.default_rng(5))
    n_reach = sum(isinstance(q, Reach) for q in queries)
    before = dict(vars(sess.stats))
    res = sess.run(queries)
    assert [(grp.kind, grp.n) for grp in sess.last_plan.groups] == [
        ("dist", len(queries))]
    assert sess.stats.reach_fused - before["reach_fused"] == n_reach
    assert sess.stats.reach_rows - before["reach_rows"] == n_reach
    want = repro.connect(fr, backend="vmap", cache="none").run(queries)
    for q, r, w in zip(queries, res, want):
        assert (r.answer, r.distance) == (w.answer, w.distance), q
        if isinstance(q, Reach):
            assert r.answer == oracle_reach(g, q.s, q.t), q
            assert r.distance == (0 if q.s == q.t else None), q
        elif q.bound is None:
            assert r.distance == oracle_dist(g, q.s, q.t), q
        else:
            d = oracle_dist(g, q.s, q.t)
            assert r.answer == (d is not None and d <= q.bound), q
    grp = sess.last_plan.groups[0]
    assert sum(r.stats.payload_bits for r in res) == fr.traffic_bits(
        "dist", batch=grp.padded_size)
    assert sum(r.stats.collective_rounds for r in res) == 1


@pytest.mark.parametrize("case", ["reach_only", "unwarmed",
                                  "warmed_without_dist", "cache_none",
                                  "shard_map"])
def test_reach_keeps_its_group_where_the_rule_does_not_apply(case):
    g, fr = _case(30, 70, 3, 12)
    queries = _reach_dist_batch(g.n, np.random.default_rng(6))
    if case == "reach_only":
        queries = [q for q in queries if isinstance(q, Reach)]
    backend = "shard_map" if case == "shard_map" else "vmap"
    sess = repro.connect(fr, backend=backend,
                         cache="none" if case == "cache_none" else "amortized")
    if case in ("reach_only", "cache_none", "shard_map"):
        # the fragmentation holds the tropical closure: only the batch,
        # the cache mode or the backend keeps the rule off
        repro.connect(fr, backend="vmap").warm(with_dist=True)
        assert fr.rvset_cache.bl_dist is not None
    elif case == "warmed_without_dist":
        sess.warm()
    res = sess.run(queries)
    assert sess.stats.reach_fused == 0
    want = plan_queries(queries, sess._resolve_automaton)
    assert [(grp.kind, grp.indices) for grp in sess.last_plan.groups] == \
        [(grp.kind, grp.indices) for grp in want.groups]
    assert "reach" in [grp.kind for grp in sess.last_plan.groups]
    for q, r in zip(queries, res):
        if isinstance(q, Reach):
            assert r.answer == oracle_reach(g, q.s, q.t), q
    if case in ("unwarmed", "warmed_without_dist"):
        # that batch's dist group built the tropical closure, so from the
        # next batch on the reach reads join it
        sess.run(queries)
        assert [grp.kind for grp in sess.last_plan.groups] == ["dist"]
        assert sess.stats.reach_fused == sum(isinstance(q, Reach)
                                             for q in queries)


@pytest.mark.parametrize("case,n_queries,want", [
    ("reach", 5, 8), ("dist", 9, 16), ("dist", 20, 16), ("rpq", 5, 0),
    ("cache_none", 5, 0), ("shard_map", 5, 0)])
def test_frag_blocks_count_the_lane_stage_blocks(case, n_queries, want):
    """A cached vmap reach or dist group relaxes its bucket's rows over
    min(bucket, k) fragment blocks (k = 16 here): the session counts them
    in ``frag_blocks`` and the group span says ``frags``; groups without
    that stage count 0."""
    from repro import tracing
    g, fr = _case(64, 160, 16, 4)
    rng = np.random.default_rng(n_queries)
    ends = rng.integers(0, g.n, size=(n_queries, 2))
    make = {"reach": Reach, "cache_none": Reach, "shard_map": Reach,
            "dist": Dist,
            "rpq": lambda s, t: Rpq(s, t, regex="0*")}[case]
    queries = [make(int(s), int(t)) for s, t in ends]
    sess = repro.connect(
        fr, backend="shard_map" if case == "shard_map" else "vmap",
        cache="none" if case == "cache_none" else "amortized")
    tracing.enable()
    try:
        res = sess.run(queries)
        spans, _ = tracing.drain()
    finally:
        tracing.disable()
    assert sess.stats.frag_blocks == want
    [grp] = [s for s in spans if s.name == "repro.session.group"]
    assert grp.attrs["frags"] == sess.stats.frag_blocks
    if case == "reach":
        for q, r in zip(queries, res):
            assert r.answer == oracle_reach(g, q.s, q.t), q
    elif case == "dist":
        for q, r in zip(queries, res):
            assert r.distance == oracle_dist(g, q.s, q.t), q


def test_bucket_padding_avoids_retraces():
    assert [bucket_size(n) for n in (1, 8, 9, 16, 17, 100)] == \
        [8, 8, 16, 16, 32, 128]
    g, fr = _case(16, 40, 2, 1)
    sess = repro.connect(fr)
    for n_batch in (1, 3, 5, 7):       # same bucket -> same compiled shape
        res = sess.run([Reach(0, i + 1) for i in range(n_batch)])
        assert len(res) == n_batch
    assert sess.last_plan.groups[0].padded_size == 8


def test_query_ir_validation():
    with pytest.raises(ValueError, match="exactly one"):
        Rpq(0, 1)
    with pytest.raises(ValueError, match="exactly one"):
        Rpq(0, 1, regex="0*", automaton=_automaton("0*"))
    with pytest.raises(ValueError, match=">= 0"):
        Reach(-1, 2)
    with pytest.raises(TypeError):
        plan_queries(["not a query"], lambda q: None)
    # IR values are hashable/comparable, incl. automaton-based RPQs (the
    # automaton holds numpy arrays; value semantics go via cache_key)
    qa_a, qa_b = _automaton("0* 1"), _automaton("0* 1")
    assert Rpq(0, 1, automaton=qa_a) == Rpq(0, 1, automaton=qa_b)
    assert Rpq(0, 1, automaton=qa_a) != Rpq(0, 1, regex="0* 1")
    assert len({Rpq(0, 1, automaton=qa_a), Rpq(0, 1, automaton=qa_b),
                Reach(0, 1), Dist(0, 1)}) == 3


def test_session_version_stamping_and_apply():
    g, fr = _case(18, 40, 2, 5)
    sess = repro.connect(fr, backend="vmap").warm()
    r0 = sess.run([Reach(0, 1)])[0]
    assert r0.cache_version == 0
    stats = sess.apply(GraphDelta.insert([(0, 1)]))
    assert stats.mode in ("repair", "recompute", "rebuild")
    r1 = sess.run([Reach(0, 1)])[0]
    assert r1.answer and r1.cache_version == r0.cache_version + 1
    assert sess.stats.updates == 1
    # uncached execution never consulted the cache -> stamped None even
    # though a cache exists on the shared fragmentation
    assert dis_reach(fr, 0, 1).cache_version is None


# ---------------------------------------------------------------------------
# shims & stats consistency
# ---------------------------------------------------------------------------

def test_deprecated_shims_removed_seed_paths_warning_free():
    """PR 8 retired the PR-4-deprecated cache-bearing shims; the seed
    one-shot entry points survive and stay warning-free."""
    import warnings as _w
    import repro.core
    g, fr = _case(12, 30, 2, 2)
    qa = _automaton("0*")
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        dis_reach(fr, 0, 1)            # seed paths stay warning-free
        dis_dist(fr, 0, 1)
        dis_rpq(fr, 0, 1, qa)
    for name in ("dis_reach_cached", "dis_dist_cached", "dis_rpq_cached",
                 "dis_reach_batch", "dis_dist_batch", "dis_rpq_batch"):
        assert not hasattr(repro.core, name), name
        assert not hasattr(repro.core.api, name), name
        assert name not in repro.core.__all__


def test_traffic_bits_consistent_across_kinds():
    g, fr = _case(30, 90, 3, 3)
    B, words = fr.B, (fr.B + 31) // 32
    assert fr.traffic_bits("reach") == B * words * 32
    assert fr.traffic_bits("dist") == B * B * 32
    assert fr.traffic_bits("bounded") == fr.traffic_bits("dist")
    qa = _automaton("0* 1")
    side = B * qa.n_states
    assert fr.traffic_bits("rpq", states=qa.n_states) == \
        side * ((side + 31) // 32) * 32
    with pytest.raises(ValueError, match="unknown query kind"):
        fr.traffic_bits("nope")
    with pytest.raises(ValueError, match="unknown query kind"):
        fr.traffic_bits("nope", batch=8)
    # every query class reports through the one helper
    assert dis_reach(fr, 0, 1).stats.payload_bits == fr.traffic_bits("reach")
    assert dis_dist(fr, 0, 1).stats.payload_bits == fr.traffic_bits("dist")
    assert dis_rpq(fr, 0, 1, qa).stats.payload_bits == \
        fr.traffic_bits("rpq", states=qa.n_states)
    # fused-batch wire format: side + 2N rows of side + 1 (the direct
    # column); Boolean kinds bitpack, the tropical wire ships raw int32
    nb, N = fr.n_boundary, 8
    assert fr.traffic_bits("reach", batch=N) == \
        (nb + 2 * N) * ((nb + 1 + 31) // 32) * 32
    assert fr.traffic_bits("dist", batch=N) == (nb + 2 * N) * (nb + 1) * 32
    sq = nb * qa.n_states
    assert fr.traffic_bits("rpq", states=qa.n_states, batch=N) == \
        (sq + 2 * N) * ((sq + 1 + 31) // 32) * 32


def test_group_traffic_sums_to_one_collective_vmap():
    """Per-group stats amortize the group's ONE collective: summed
    payload_bits over every fused group equal the wire size of that
    group's single collective, and exactly one collective round is
    reported per group (not one per query)."""
    g, fr = _case(20, 55, 3, 9)
    sess = repro.connect(fr, backend="vmap")
    qa = _automaton(REGEXES[0])
    queries = [Reach(0, 5), Reach(3, 3), Reach(1, 2), Dist(0, 7),
               Dist(2, 2, bound=1), Rpq(4, 9, automaton=qa),
               Rpq(5, 5, automaton=qa), Dist(6, 1, bound=3)]
    results = sess.run(queries)
    for grp in sess.last_plan.groups:
        states = 1 if grp.automaton is None else grp.automaton.n_states
        want = fr.traffic_bits(grp.kind, states=states,
                               batch=grp.padded_size)
        assert sum(results[i].stats.payload_bits
                   for i in grp.indices) == want, grp.kind
        assert sum(results[i].stats.collective_rounds
                   for i in grp.indices) == 1, grp.kind


# ---------------------------------------------------------------------------
# server: rpq kind, submit validation, batches spanning a delta
# ---------------------------------------------------------------------------

def test_sharded_device_inputs_memoized_until_delta(shard_map_report):
    """The batched sharded engines' device uploads (edge lists + boundary
    gathers) are built once per fragmentation state: repeat batches reuse
    the memo, and an apply_delta (which mutates the host arrays in place)
    invalidates it via arrays_version.  The uploads are placed shard by
    shard on the mesh they serve (a different placement missing the memo
    is checked on 8 devices in the subprocess below)."""
    from repro.core import Placement, distributed
    g, fr = _case(16, 40, 2, 3)
    mesh = distributed.fragment_mesh(1)
    pl = Placement.round_robin(fr.k, 1)
    m1 = distributed._device_inputs(fr, pl, mesh)
    assert distributed._device_inputs(fr, pl, mesh) is m1   # steady state
    assert m1["arrs"]["esrc"].sharding.mesh == mesh
    v0 = fr.arrays_version
    fr.apply_delta(GraphDelta.insert([(0, 1)]))
    assert fr.arrays_version == v0 + 1
    m2 = distributed._device_inputs(fr, pl, mesh)
    assert m2 is not m1 and m2["version"] == fr.arrays_version
    assert distributed._device_inputs(fr, pl, mesh) is m2
    assert shard_map_report["memo_ok"], shard_map_report


def test_server_submit_validates_kind_and_args():
    g, fr = _case(10, 20, 2, 6)
    srv = QueryServer(fr, batch_size=4, warm=False)
    with pytest.raises(ValueError, match="unknown query kind 'reachh'"):
        srv.submit(0, 1, kind="reachh")
    with pytest.raises(ValueError, match="bound"):
        srv.submit(0, 1, kind="bounded")
    with pytest.raises(ValueError, match="only valid for kind='bounded'"):
        srv.submit(0, 1, kind="dist", bound=3)    # meant kind="bounded"
    with pytest.raises(ValueError, match="exactly one"):
        srv.submit(0, 1, kind="rpq")
    with pytest.raises(ValueError, match="only valid"):
        srv.submit(0, 1, kind="reach", regex="0*")
    assert srv.pending() == 0          # rejected submits never enqueue


def test_server_serves_rpq_kind():
    g, fr = _case(18, 50, 3, 7)
    srv = QueryServer(fr, batch_size=4, start=False)
    qa = _automaton(REGEXES[1])
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(9):
        s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
        # alternate regex / prebuilt automaton — same fused group either way
        if i % 2:
            reqs.append(srv.submit(s, t, kind="rpq", regex=REGEXES[1]))
        else:
            reqs.append(srv.submit(s, t, kind="rpq", automaton=qa))
    srv.flush()
    for r in reqs:
        assert r.value == oracle_rpq(g, r.s, r.t, qa), (r.s, r.t)
        assert r.cache_version is not None


def test_server_mixed_batch_spanning_delta_snapshots():
    """Queries on both sides of a submit_delta answer against their own
    snapshot, for all three kinds in one flush."""
    g, fr = _case(16, 26, 2, 8)
    srv = QueryServer(fr, batch_size=8, start=False)
    qa = _automaton("(0|1|2)*")
    rng = np.random.default_rng(3)
    pairs = [(int(rng.integers(g.n)), int(rng.integers(g.n)))
             for _ in range(4)]
    pre = ([srv.submit(s, t) for s, t in pairs]
           + [srv.submit(s, t, kind="dist") for s, t in pairs]
           + [srv.submit(s, t, kind="rpq", automaton=qa)
              for s, t in pairs])
    pre_want = ([oracle_reach(g, s, t) for s, t in pairs]
                + [oracle_dist(g, s, t) for s, t in pairs]
                + [oracle_rpq(g, s, t, qa) for s, t in pairs])
    delta = GraphDelta.insert(
        [(int(rng.integers(g.n)), int(rng.integers(g.n)))
         for _ in range(3)])
    upd = srv.submit_delta(delta)
    post = ([srv.submit(s, t) for s, t in pairs]
            + [srv.submit(s, t, kind="rpq", automaton=qa)
               for s, t in pairs])
    srv.flush()
    g2 = fr.g                                  # post-delta graph
    post_want = ([oracle_reach(g2, s, t) for s, t in pairs]
                 + [oracle_rpq(g2, s, t, qa) for s, t in pairs])
    assert [r.value for r in pre] == pre_want
    assert [r.value for r in post] == post_want
    assert upd.value is not None and srv.updates_applied == 1
    # snapshot stamps: everything before the delta at version v, after > v
    v_pre = {r.cache_version for r in pre}
    v_post = {r.cache_version for r in post}
    assert len(v_pre) == 1 and len(v_post) == 1
    assert v_post.pop() > v_pre.pop()


# ---------------------------------------------------------------------------
# shard_map backend over 8 fake devices (subprocess, like test_guarantees)
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "__SRC__")
sys.path.insert(0, "__TESTS__")
import numpy as np
import repro
from repro.core import (Dist, GraphDelta, Reach, Rpq, build_query_automaton,
                        fragment_graph)
from repro.core.distributed import fragment_mesh
from repro.graph import erdos_renyi, random_partition
from repro.serve import QueryServer
from oracles import oracle_dist, oracle_reach, oracle_rpq

g = erdos_renyi(40, 120, n_labels=3, seed=7)
fr = fragment_graph(g, random_partition(g, 8, 1), 8)
sess = repro.connect(fr)                      # auto -> shard_map on 8 devs
qa = build_query_automaton("(0|1)*", lambda x: int(x))
rng = np.random.default_rng(2)
queries, want = [], []
for _ in range(12):
    s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
    kind = int(rng.integers(3))
    if kind == 0:
        queries.append(Reach(s, t)); want.append(oracle_reach(g, s, t))
    elif kind == 1:
        queries.append(Dist(s, t)); want.append(oracle_dist(g, s, t))
    else:
        queries.append(Rpq(s, t, automaton=qa))
        want.append(oracle_rpq(g, s, t, qa))
res = sess.run(queries)
got = [r.distance if isinstance(q, Dist) else r.answer
       for q, r in zip(queries, res)]
kinds_seen = sorted({grp.kind for grp in sess.last_plan.groups})

# summed per-group QueryStats == the wire of the group's ONE collective
bits_ok = True
for grp in sess.last_plan.groups:
    states = 1 if grp.automaton is None else grp.automaton.n_states
    total = fr.traffic_bits(grp.kind, states=states, batch=grp.padded_size)
    bits_ok &= sum(res[i].stats.payload_bits for i in grp.indices) == total
    bits_ok &= sum(res[i].stats.collective_rounds for i in grp.indices) == 1

# backend='auto' judges shard_map-vs-vmap against an explicit mesh, not
# the process device count (8 devices here, mesh of 2): with the k >> d
# packing layer a 2-device mesh HOLDS 4 fragments (2 per device), so auto
# picks shard_map; a mesh larger than fr.k still cannot work (a fragment
# is never split across devices) and must fall back / refuse instead of
# crashing inside the engine
mesh2 = fragment_mesh(2)
mesh4 = fragment_mesh(4)
from repro.core import Placement, distributed
fr_m = fragment_graph(g, random_partition(g, 4, 0), 4)
m1 = distributed._device_inputs(fr_m, Placement.round_robin(4, 2), mesh2)
# a different placement misses the (version, placement, mesh) memo key,
# and every packed upload is split across the mesh, not stacked on one device
memo_ok = (distributed._device_inputs(fr_m, Placement.round_robin(4, 2),
                                      mesh2) is m1
           and distributed._device_inputs(fr_m, Placement.balanced(fr_m, 4),
                                          mesh4) is not m1
           and len(m1["arrs"]["esrc"].sharding.device_set) == 2)
fr4 = fragment_graph(g, random_partition(g, 4, 0), 4)
fr2 = fragment_graph(g, random_partition(g, 2, 0), 2)
small = repro.connect(fr4, mesh=mesh2)        # 4 frags packed on 2 devices
auto_small_mesh = small.backend                     # must be shard_map now
small_res = small.run([Reach(0, 5), Dist(1, 7)])
small_ok = (small_res[0].answer == oracle_reach(g, 0, 5)
            and small_res[1].distance == oracle_dist(g, 1, 7)
            and small.placement.d == 2 and small.placement.fpd == 2)
auto_big_mesh = repro.connect(fr2, mesh=mesh4).backend       # must be vmap
auto_fit_mesh = repro.connect(fr2, mesh=mesh2).backend  # must be shard_map
try:
    repro.connect(fr2, backend="shard_map", mesh=mesh4)
    big_mesh_raises = False
except ValueError:
    big_mesh_raises = True
sess2 = repro.connect(fr2, mesh=mesh2)
res2 = sess2.run([Reach(0, 5), Dist(1, 7), Rpq(2, 9, automaton=qa)])
mesh_ok = (res2[0].answer == oracle_reach(g, 0, 5)
           and res2[1].distance == oracle_dist(g, 1, 7)
           and res2[2].answer == oracle_rpq(g, 2, 9, qa))

# server over the shard_map backend: a mixed batch of all three kinds
# spanning a submit_delta answers each side against its own snapshot
gs = erdos_renyi(24, 40, n_labels=3, seed=8)
frs = fragment_graph(gs, random_partition(gs, 4, 3), 4,
                     reserve_boundary=8, reserve_edges=16, reserve_stubs=8)
srv = QueryServer(frs, batch_size=16, start=False)
qa2 = build_query_automaton("(0|1|2)*", lambda x: int(x))
pairs = [(int(rng.integers(gs.n)), int(rng.integers(gs.n)))
         for _ in range(4)]
def submit_all():
    return ([srv.submit(s, t) for s, t in pairs]
            + [srv.submit(s, t, kind="dist") for s, t in pairs]
            + [srv.submit(s, t, kind="rpq", automaton=qa2)
               for s, t in pairs])
def want_all(gg):
    return ([oracle_reach(gg, s, t) for s, t in pairs]
            + [oracle_dist(gg, s, t) for s, t in pairs]
            + [oracle_rpq(gg, s, t, qa2) for s, t in pairs])
pre = submit_all()
pre_want = want_all(gs)
upd = srv.submit_delta(GraphDelta.insert(
    [(int(rng.integers(gs.n)), int(rng.integers(gs.n))) for _ in range(3)]))
post = submit_all()
srv.flush()
post_want = want_all(frs.g)                   # post-delta graph
v_pre = {r.cache_version for r in pre}
v_post = {r.cache_version for r in post}
server_ok = ([r.value for r in pre] == pre_want
             and [r.value for r in post] == post_want
             and len(v_pre) == 1 and len(v_post) == 1
             and v_post.pop() > v_pre.pop())

print(json.dumps({"backend": sess.backend, "ok": got == want,
                  "kinds": kinds_seen, "bits_ok": bool(bits_ok),
                  "groups": sess.last_plan.n_groups,
                  "executions": sess.stats.executions,
                  "auto_small_mesh": auto_small_mesh,
                  "small_ok": bool(small_ok),
                  "auto_big_mesh": auto_big_mesh,
                  "auto_fit_mesh": auto_fit_mesh,
                  "big_mesh_raises": bool(big_mesh_raises),
                  "mesh_ok": bool(mesh_ok),
                  "server_backend": srv.session.backend,
                  "update_mode": upd.value.mode,
                  "server_ok": bool(server_ok),
                  "memo_ok": bool(memo_ok),
                  "degraded_groups": sum(x.stats.degraded_groups for x in (
                      sess, small, sess2, srv.session))}))
"""


@pytest.fixture(scope="module")
def shard_map_report():
    here = os.path.dirname(__file__)
    code = (_SUBPROC
            .replace("__SRC__", os.path.abspath(os.path.join(here, "..",
                                                             "src")))
            .replace("__TESTS__", os.path.abspath(here)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_session_shard_map_mixed_batch_subprocess(shard_map_report):
    rep = shard_map_report
    assert rep["backend"] == "shard_map"
    assert rep["ok"], rep
    assert rep["executions"] == rep["groups"]
    # exact answers must come from the sharded engines themselves, not
    # from the vmap fallback that serves a failed sharded group
    assert rep["degraded_groups"] == 0, rep
    # the random draw produced all three kinds -> all three sharded paths ran
    assert rep["kinds"] == ["dist", "reach", "rpq"], rep


def test_shard_map_group_traffic_sums_to_one_collective(shard_map_report):
    """Summed QueryStats over any fused shard_map group equals the wire
    size of that group's single collective (one round per group)."""
    assert shard_map_report["bits_ok"], shard_map_report


def test_auto_backend_respects_explicit_mesh(shard_map_report):
    """backend='auto' with an explicit mesh decides from the mesh's device
    count: a 2-device mesh holds 4 fragments (2 packed per device) so auto
    picks shard_map and answers match the oracle; a mesh larger than fr.k
    must fall back to vmap (auto) or raise up front (explicit) instead of
    crashing inside the sharded engine."""
    rep = shard_map_report
    assert rep["auto_small_mesh"] == "shard_map", rep
    assert rep["small_ok"], rep
    assert rep["auto_big_mesh"] == "vmap", rep
    assert rep["auto_fit_mesh"] == "shard_map", rep
    assert rep["big_mesh_raises"], rep
    assert rep["mesh_ok"], rep


def test_server_shard_map_mixed_batch_spanning_delta(shard_map_report):
    """QueryServer on the shard_map backend: all three kinds in one flush,
    split across a submit_delta, answer against their own snapshots."""
    rep = shard_map_report
    assert rep["server_backend"] == "shard_map", rep
    assert rep["server_ok"], rep
    assert rep["update_mode"] in ("repair_sharded", "repair", "recompute",
                                  "rebuild"), rep
