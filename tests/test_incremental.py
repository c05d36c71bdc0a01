"""Incremental rvset-cache maintenance vs from-scratch rebuild and oracles.

The contract (DESIGN.md Sec. 3.5): after any stream of edge deltas, the
``apply_delta``-maintained cache answers exactly like a cache rebuilt from
scratch on the updated graph, and both match the numpy/networkx oracles —
for plain reachability, distances, and regular (RPQ) queries.  Repair is an
optimization, never a semantic change.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (GraphDelta, apply_delta, build_query_automaton,
                        fragment_graph, get_rvset_cache, prepare_rvset_cache)
# The rebuild-vs-maintained comparisons below want the raw batched kernels
# (with the -1 "unreachable" sentinel), not session-level QueryResults; the
# public dis_*_batch shims were removed in PR 8, so reach into the internal
# cache engines directly.
from repro.core.cache import dis_dist_batch, dis_reach_batch, rpq_cached
from repro.core.incremental import (REBUILD_DEBT, changed_row_ids,
                                    pad_row_ids)
from repro.graph import erdos_renyi, random_partition
from repro.graph.graph import Graph
from repro.serve import DeltaApplyFailed, QueryServer

from oracles import oracle_dist, oracle_reach, oracle_rpq


def _dynamic_case(n, m, k, seed, **reserve):
    g = erdos_renyi(n, m, n_labels=3, seed=seed)
    part = random_partition(g, k, seed)
    kw = dict(reserve_boundary=8, reserve_edges=24, reserve_stubs=12)
    kw.update(reserve)
    return g, part, fragment_graph(g, part, k, **kw)


def _draw_delta(data, fr, n_add, n_del):
    n = fr.g.n
    adds = [(data.draw(st.integers(0, n - 1)), data.draw(st.integers(0, n - 1)))
            for _ in range(n_add)]
    dels, taken = [], set()
    for _ in range(n_del):
        if fr.g.m == 0:
            break
        e = data.draw(st.integers(0, fr.g.m - 1))
        if e in taken:                    # one delete per edge occurrence
            continue
        taken.add(e)
        dels.append((int(fr.g.src[e]), int(fr.g.dst[e])))
    return GraphDelta(add_src=[u for u, _ in adds], add_dst=[v for _, v in adds],
                      del_src=[u for u, _ in dels], del_dst=[v for _, v in dels])


def _check_against_rebuild_and_oracle(fr, pairs):
    """maintained == rebuilt-from-scratch == oracle, reach + dist."""
    fresh = fragment_graph(fr.g, fr.part, fr.k)
    got = dis_reach_batch(fr, pairs)
    ref = dis_reach_batch(fresh, pairs)
    got_d = dis_dist_batch(fr, pairs)
    ref_d = dis_dist_batch(fresh, pairs)
    for (s, t), a, ra, d, rd in zip(pairs, got, ref, got_d, ref_d):
        want = oracle_reach(fr.g, s, t)
        want_d = oracle_dist(fr.g, s, t)
        assert bool(a) == bool(ra) == want, (s, t)
        assert int(d) == int(rd), (s, t)
        assert (None if d < 0 else int(d)) == want_d, (s, t)


# ---------------------------------------------------------------------------
# property: maintained cache == rebuilt cache == oracle on delta streams
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(data=st.data())
def test_property_delta_stream_reach_dist(data):
    n = data.draw(st.integers(6, 20), label="n")
    m = data.draw(st.integers(0, 40), label="m")
    k = data.draw(st.integers(1, 4), label="k")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    g, part, fr = _dynamic_case(n, m, k, seed)
    prepare_rvset_cache(fr, with_dist=True)
    for _ in range(3):
        n_add = data.draw(st.integers(0, 4), label="n_add")
        n_del = data.draw(st.integers(0, 2), label="n_del")
        delta = _draw_delta(data, fr, n_add, n_del)
        apply_delta(fr, delta)
        pairs = [(data.draw(st.integers(0, n - 1)),
                  data.draw(st.integers(0, n - 1))) for _ in range(4)]
        _check_against_rebuild_and_oracle(fr, pairs)


@settings(max_examples=4, deadline=None)
@given(data=st.data())
def test_property_delta_stream_rpq(data):
    n = data.draw(st.integers(8, 16), label="n")
    m = data.draw(st.integers(5, 30), label="m")
    k = data.draw(st.integers(1, 3), label="k")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    g, part, fr = _dynamic_case(n, m, k, seed)
    qa = build_query_automaton(
        data.draw(st.sampled_from(["0* 1*", "(0|1)* 2", ". . ."]),
                  label="regex"), lambda x: int(x))
    prepare_rvset_cache(fr)
    for _ in range(2):
        delta = _draw_delta(data, fr, data.draw(st.integers(1, 3)),
                            data.draw(st.integers(0, 1)))
        apply_delta(fr, delta)
        fresh = fragment_graph(fr.g, fr.part, fr.k)
        for _ in range(3):
            s = data.draw(st.integers(0, n - 1))
            t = data.draw(st.integers(0, n - 1))
            want = oracle_rpq(fr.g, s, t, qa)
            assert rpq_cached(fr, s, t, qa) == want, (s, t)
            assert rpq_cached(fresh, s, t, qa) == want, (s, t)


# ---------------------------------------------------------------------------
# cache invalidation edge cases
# ---------------------------------------------------------------------------

def test_cross_edge_landing_on_query_target():
    """A delta whose cross edge lands exactly on a query target t: the
    t-column must pick up the new boundary row (alias-column case)."""
    # two fragments: 0|1|2 -> frag 0, 3|4|5 -> frag 1; t = 5 only reachable
    # through the inserted cross edge 2 -> 5
    g = Graph(6, np.array([0, 1, 3]), np.array([1, 2, 4]),
              np.zeros(6, np.int32))
    part = np.array([0, 0, 0, 1, 1, 1], np.int32)
    fr = fragment_graph(g, part, 2, reserve_boundary=4, reserve_edges=8,
                        reserve_stubs=4)
    prepare_rvset_cache(fr, with_dist=True)
    assert not dis_reach_batch(fr, [(0, 5)])[0]
    st1 = apply_delta(fr, GraphDelta.insert([(2, 5)]))
    assert st1.new_boundary == 1          # 5 became a boundary in-node
    assert bool(dis_reach_batch(fr, [(0, 5)])[0])
    assert int(dis_dist_batch(fr, [(0, 5)])[0]) == 3
    # and a second cross edge onto the (now-boundary) target: alias path
    st2 = apply_delta(fr, GraphDelta.insert([(1, 5)]))
    assert st2.new_boundary == 0
    assert int(dis_dist_batch(fr, [(0, 5)])[0]) == 2
    _check_against_rebuild_and_oracle(fr, [(0, 5), (5, 0), (3, 5), (0, 4)])


def test_nonboundary_node_becomes_boundary_in_node():
    """Activating a spare boundary slot must not change any array shape
    (jit stability) while making the new in-node's row live."""
    g, part, fr = _dynamic_case(18, 25, 3, seed=4)
    prepare_rvset_cache(fr)
    cache = get_rvset_cache(fr)
    B0, closure_shape = fr.B, cache.closure.shape
    nb_active0 = fr.nb_active
    # find a node with no incoming cross edge and a source in another frag
    cross_dst = set(g.dst[part[g.src] != part[g.dst]].tolist())
    w = next(v for v in range(g.n) if v not in cross_dst)
    u = next(u for u in range(g.n) if part[u] != part[w])
    st1 = apply_delta(fr, GraphDelta.insert([(u, w)]))
    assert st1.new_boundary == 1
    assert fr.nb_active == nb_active0 + 1
    assert fr.b_index[w] == nb_active0    # landed in the first spare slot
    assert fr.B == B0                     # static shapes preserved
    assert cache.closure.shape == closure_shape
    pairs = [(u, w), (w, u)] + [(s, w) for s in range(0, g.n, 5)]
    _check_against_rebuild_and_oracle(fr, pairs)


def test_empty_delta_is_noop_with_array_identity():
    g, part, fr = _dynamic_case(14, 20, 2, seed=6)
    prepare_rvset_cache(fr, with_dist=True)
    cache = get_rvset_cache(fr)
    arrays, bl, C = cache.arrays, cache.bl_frontier, cache.closure
    bl_d, Cd, v = cache.bl_dist, cache.dist_closure, cache.version
    stats = apply_delta(fr, GraphDelta())
    assert stats.mode == "noop"
    assert cache.arrays is arrays         # same objects, not equal copies
    assert cache.bl_frontier is bl and cache.closure is C
    assert cache.bl_dist is bl_d and cache.dist_closure is Cd
    assert cache.version == v and fr.rvset_cache is cache


def test_deletions_recompute_then_debt_forces_rebuild():
    g, part, fr = _dynamic_case(20, 60, 3, seed=8)
    prepare_rvset_cache(fr)
    rng = np.random.default_rng(0)
    modes = []
    for _ in range(12):
        e = int(rng.integers(fr.g.m))
        stats = apply_delta(
            fr, GraphDelta.delete([(int(fr.g.src[e]), int(fr.g.dst[e]))]))
        modes.append(stats.mode)
        if stats.mode == "rebuild":
            assert stats.reason == "repair debt"
            break
    assert modes[0] == "recompute"
    assert "rebuild" in modes             # debt counter eventually trips
    assert len(modes) <= int(REBUILD_DEBT / 0.5) + 1
    pairs = [(int(rng.integers(g.n)), int(rng.integers(g.n)))
             for _ in range(6)]
    _check_against_rebuild_and_oracle(fr, pairs)


def test_capacity_overflow_falls_back_to_rebuild():
    g, part, fr = _dynamic_case(16, 30, 2, seed=3, reserve_boundary=0,
                                reserve_edges=0, reserve_stubs=0)
    prepare_rvset_cache(fr)
    other = np.nonzero(part != part[0])[0]
    adds = [(0, int(v)) for v in other[:3]] * 8   # blow the edge headroom
    stats = apply_delta(fr, GraphDelta.insert(adds))
    assert stats.mode == "rebuild"
    _check_against_rebuild_and_oracle(fr, [(0, int(other[0])), (3, 9)])


def test_changed_row_padding_buckets():
    g, part, fr = _dynamic_case(20, 50, 3, seed=1)
    dirty = np.zeros(fr.k, dtype=bool)
    dirty[0] = True
    rows = changed_row_ids(fr, dirty)
    assert set(fr.boundary_owner()[rows]) <= {0}
    padded = pad_row_ids(rows, pad=8)
    assert len(padded) % 8 == 0
    assert set(padded) == set(rows)       # padding repeats, never invents


# ---------------------------------------------------------------------------
# serving loop: interleaved updates with snapshot consistency
# ---------------------------------------------------------------------------

def test_server_interleaved_updates_snapshot_consistency():
    g, part, fr = _dynamic_case(24, 30, 3, seed=11)
    srv = QueryServer(fr, batch_size=4, start=False)
    rng = np.random.default_rng(1)
    s = t = None
    for _ in range(400):
        a, b = int(rng.integers(g.n)), int(rng.integers(g.n))
        if a != b and not oracle_reach(g, a, b):
            s, t = a, b
            break
    assert s is not None
    q_before = srv.submit(s, t)
    upd = srv.submit_delta(GraphDelta.insert([(s, t)]))
    q_after = srv.submit(s, t)
    srv.flush()
    # the pre-update query saw the pre-delta snapshot
    assert q_before.result() is False and q_after.result() is True
    assert upd.value.mode in ("repair", "recompute")
    assert q_before.cache_version < q_after.cache_version
    assert srv.updates_applied == 1
    # mixed stream stays correct against the evolving oracle
    for _ in range(2):
        reqs = [srv.submit(int(rng.integers(g.n)), int(rng.integers(g.n)))
                for _ in range(7)]
        pre_g = fr.g
        srv.submit_delta(GraphDelta.insert(
            [(int(rng.integers(g.n)), int(rng.integers(g.n)))]))
        srv.flush()
        for r in reqs:
            assert r.result() == oracle_reach(pre_g, r.s, r.t)


def test_server_failed_update_preserves_later_requests():
    """A bad update resolves ``failed`` (typed, rolled back) and must not
    eat the queue: pre- and post-update queries are served in the same
    drain (PR 7 replaced the old raise-out-of-drain behavior)."""
    g, part, fr = _dynamic_case(16, 24, 2, seed=13)
    srv = QueryServer(fr, batch_size=4, start=False)
    present = set(zip(g.src.tolist(), g.dst.tolist()))
    missing = next((u, v) for u in range(g.n) for v in range(g.n)
                   if (u, v) not in present)
    q_before = srv.submit(0, 1)
    upd = srv.submit_delta(GraphDelta.delete([missing]))  # nonexistent edge
    q_after = srv.submit(2, 3)
    served = srv.flush()
    assert q_before.result() == oracle_reach(g, 0, 1)     # flushed first
    assert upd.status == "failed" and srv.updates_failed == 1
    assert isinstance(upd.error, DeltaApplyFailed) and upd.error.rolled_back
    assert isinstance(upd.error.cause, ValueError)
    assert q_after.result() == oracle_reach(g, 2, 3)      # not blocked
    assert srv.pending() == 0
    assert sorted(map(id, served)) == sorted(map(id, [q_before, upd, q_after]))


# ---------------------------------------------------------------------------
# sharded repair: update collective ships only the changed bitpacked rows
# ---------------------------------------------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "__SRC__")
import numpy as np
from repro.graph import erdos_renyi, random_partition
from repro.graph.graph import bfs_reachable
from repro.core import fragment_graph, prepare_rvset_cache, GraphDelta
from repro.core.cache import dis_reach_batch
from repro.core import incremental
from repro.core.distributed import (apply_delta_sharded, fragment_mesh,
                                    lower_update_hlo)

g = erdos_renyi(48, 120, n_labels=4, seed=5)
k = 8
part = random_partition(g, k, seed=2)
fr = fragment_graph(g, part, k, reserve_boundary=8, reserve_edges=32,
                    reserve_stubs=16)
prepare_rvset_cache(fr)
mesh = fragment_mesh(k)
rng = np.random.default_rng(0)

ok, modes, on_one_device = True, [], True
for step in range(3):
    f = int(rng.integers(k))
    mine = np.nonzero(part == f)[0]
    other = np.nonzero(part != f)[0]
    adds = [(int(rng.choice(mine)), int(rng.choice(mine))) for _ in range(2)]
    adds += [(int(rng.choice(mine)), int(rng.choice(other)))]
    st = apply_delta_sharded(fr, GraphDelta.insert(adds), mesh=mesh)
    modes.append(st.mode)
    on_one_device &= all(
        len(getattr(fr.rvset_cache, name).sharding.device_set) == 1
        for name in ("closure", "bl_frontier"))
    pairs = [(int(rng.integers(g.n)), int(rng.integers(g.n)))
             for _ in range(24)]
    got = dis_reach_batch(fr, pairs)
    for (s, t), a in zip(pairs, got):
        ok &= bool(a) == bool(bfs_reachable(fr.g, s)[t])

row_ids = incremental.pad_row_ids(np.arange(3), pad=8, cap=fr.n_boundary)
warm = np.zeros((fr.k, fr.s_max, fr.n_max + 1), dtype=bool)
hlo = lower_update_hlo(fr, warm, row_ids, mesh=mesh)
from repro.analysis import parse_program
model = parse_program(hlo)
words = (fr.n_boundary + 31) // 32
shape_ok = any(c.results and c.results[0].dtype == "ui32"
               and c.results[0].dims == (len(row_ids), words)
               for c in model.collectives)
print(json.dumps({"ok": bool(ok), "modes": modes,
                  "on_one_device": bool(on_one_device),
                  "n_collectives": len(model.collectives),
                  "payload_shape_ok": bool(shape_ok),
                  "rows": int(len(row_ids)), "nb": int(fr.n_boundary)}))
"""


@pytest.fixture(scope="module")
def sharded_update_report():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROC.replace("__SRC__", os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_repair_correct(sharded_update_report):
    assert sharded_update_report["ok"]
    assert set(sharded_update_report["modes"]) == {"repair_sharded"}
    # the repaired host cache stays on one device: spread over the mesh,
    # its next closure update would need Pallas kernels partitioned, which
    # the TPU refuses
    assert sharded_update_report["on_one_device"], sharded_update_report


def test_sharded_update_ships_changed_rows_only(sharded_update_report):
    """One collective; its payload is [changed_rows, ceil(nb/32)] uint32 —
    rows that did not change never hit the wire."""
    assert sharded_update_report["n_collectives"] == 1
    assert sharded_update_report["payload_shape_ok"]
    assert sharded_update_report["rows"] < sharded_update_report["nb"]
