"""Amortized rvset cache + batched session execution vs the seed path
and oracles.

The cached/batched evaluation (a ``repro.connect`` session over
core.cache) must answer exactly like the seed single-query engine
(core.api) and the networkx oracles on arbitrary graph x fragmentation x
query — the cache is an optimization, never a semantic change.  (The
PR-4-deprecated ``dis_*_cached`` / ``dis_*_batch`` shims these tests
used to drive were removed in PR 8; sessions are the one cached entry
point.)
"""
import zlib

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import connect
from repro.core import (Dist, Reach, build_query_automaton, dis_dist,
                        dis_reach, dis_rpq, fragment_graph, get_rvset_cache,
                        prepare_rvset_cache)
from repro.core import cache as _cache
from repro.core import engine
from repro.graph import erdos_renyi, random_partition
from repro.serve import QueryServer

from oracles import oracle_dist, oracle_reach, oracle_rpq


def _case(n, m, k, seed):
    g = erdos_renyi(n, m, n_labels=4, seed=seed)
    return g, fragment_graph(g, random_partition(g, k, seed), k)


# ---------------------------------------------------------------------------
# cached/batched == seed == oracle
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_property_batched_reach_matches_seed_and_oracle(data):
    n = data.draw(st.integers(4, 24), label="n")
    m = data.draw(st.integers(0, 60), label="m")
    k = data.draw(st.integers(1, 5), label="k")
    seed = data.draw(st.integers(0, 10_000), label="seed")
    g = erdos_renyi(n, m, n_labels=3, seed=seed)
    part = np.asarray(
        data.draw(st.lists(st.integers(0, k - 1), min_size=n, max_size=n),
                  label="part"), dtype=np.int32)
    fr = fragment_graph(g, part, k)
    pairs = [(data.draw(st.integers(0, n - 1)), data.draw(st.integers(0, n - 1)))
             for _ in range(4)]
    got = connect(fr).run([Reach(s, t) for s, t in pairs])
    for (s, t), r in zip(pairs, got):
        want = oracle_reach(g, s, t)
        assert r.answer == want
        assert dis_reach(fr, s, t).answer == want


@settings(max_examples=15, deadline=None)
@given(data=st.data())
def test_property_batched_dist_matches_oracle(data):
    n = data.draw(st.integers(4, 20))
    m = data.draw(st.integers(0, 50))
    k = data.draw(st.integers(1, 4))
    seed = data.draw(st.integers(0, 10_000))
    g = erdos_renyi(n, m, n_labels=3, seed=seed)
    fr = fragment_graph(g, random_partition(g, k, seed), k)
    pairs = [(data.draw(st.integers(0, n - 1)), data.draw(st.integers(0, n - 1)))
             for _ in range(4)]
    got = connect(fr).run([Dist(s, t) for s, t in pairs])
    for (s, t), r in zip(pairs, got):
        assert r.distance == oracle_dist(g, s, t)


@pytest.mark.parametrize("seed", range(4))
def test_cached_single_query_session(seed):
    rng = np.random.default_rng(seed)
    g, fr = _case(int(rng.integers(8, 36)), int(rng.integers(5, 110)),
                  int(rng.integers(1, 5)), seed)
    sess = connect(fr)
    for _ in range(8):
        s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
        assert sess.reach(s, t) == oracle_reach(g, s, t)
        assert sess.dist(s, t).distance == oracle_dist(g, s, t)
    # bounded semantics agree with the seed path (answer AND distance:
    # a failed bounded query reports no distance on both paths)
    for bound in (0, 1, 3):
        s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
        got = sess.dist(s, t, bound=bound)
        want = dis_dist(fr, s, t, bound=bound)
        assert got.answer == want.answer
        assert got.distance == want.distance


@pytest.mark.parametrize("regex", ["0* 1*", "(0|1)* 2", ". . .", "0+ (1|2)*"])
def test_cached_rpq_matches_seed_and_oracle(regex):
    # crc32, not hash(): string hashing is salted per process and would
    # make the drawn pairs irreproducible across runs
    rng = np.random.default_rng(zlib.crc32(regex.encode()))
    g, fr = _case(18, 50, 3, int(rng.integers(100)))
    qa = build_query_automaton(regex, lambda x: int(x))
    sess = connect(fr)
    for _ in range(6):
        s, t = int(rng.integers(g.n)), int(rng.integers(g.n))
        want = oracle_rpq(g, s, t, qa)
        assert dis_rpq(fr, s, t, qa).answer == want
        assert sess.rpq(s, t, automaton=qa) == want


def test_rpq_closure_cached_per_automaton():
    g, fr = _case(16, 40, 2, 0)
    sess = connect(fr)
    qa = build_query_automaton("0* 1", lambda x: int(x))
    sess.rpq(0, 5, automaton=qa)
    cache = get_rvset_cache(fr)
    assert len(cache.rpq_closures) == 1
    sess.rpq(1, 6, automaton=qa)           # same automaton: no new closure
    assert len(cache.rpq_closures) == 1
    qb = build_query_automaton("1* 0", lambda x: int(x))
    sess.rpq(0, 5, automaton=qb)
    assert len(cache.rpq_closures) == 2


def test_product_closure_eviction_is_lru_not_fifo(monkeypatch):
    """A cache hit must refresh recency: a server alternating
    MAX_RPQ_CLOSURES + 1 regexes with one hot one must keep the hot
    closure instead of rebuilding it on every query (FIFO would evict the
    oldest-*inserted*, i.e. the hot one)."""
    from repro.core import cache as cache_mod
    monkeypatch.setattr(cache_mod, "MAX_RPQ_CLOSURES", 2)
    g, fr = _case(16, 40, 2, 4)
    qa_hot = build_query_automaton("0*", lambda x: int(x))
    qa_b = build_query_automaton("1*", lambda x: int(x))
    qa_c = build_query_automaton("2*", lambda x: int(x))
    c_hot = cache_mod.product_closure(fr, qa_hot)
    cache_mod.product_closure(fr, qa_b)
    # hit the hot automaton: same object back, recency refreshed
    assert cache_mod.product_closure(fr, qa_hot) is c_hot
    cache_mod.product_closure(fr, qa_c)      # evicts qa_b (LRU), not hot
    keys = set(fr.rvset_cache.rpq_closures)
    assert qa_hot.cache_key() in keys and qa_c.cache_key() in keys
    assert qa_b.cache_key() not in keys
    assert cache_mod.product_closure(fr, qa_hot) is c_hot  # never rebuilt


# ---------------------------------------------------------------------------
# cache mechanics + stats
# ---------------------------------------------------------------------------

def test_cache_is_built_once_and_reused():
    g, fr = _case(20, 60, 3, 7)
    assert fr.rvset_cache is None
    c1 = prepare_rvset_cache(fr)
    c2 = get_rvset_cache(fr)
    assert c1 is c2 and fr.rvset_cache is c1
    # dist parts attach lazily to the same cache object
    assert c1.bl_dist is None
    connect(fr).run([Dist(0, 1)])
    assert c1.bl_dist is not None


def test_payload_bits_report_bitpacked_size():
    g, fr = _case(30, 90, 3, 3)
    B = fr.B
    words = (B + 31) // 32
    res = dis_reach(fr, 0, 1)
    assert res.stats.payload_bits == B * words * 32
    qa = build_query_automaton("0*", lambda x: int(x))
    side = B * qa.n_states
    assert (dis_rpq(fr, 0, 1, qa).stats.payload_bits ==
            side * ((side + 31) // 32) * 32)


def test_empty_and_degenerate_batches():
    g, fr = _case(10, 20, 2, 1)
    sess = connect(fr)
    assert sess.run([]) == []
    assert sess.reach(3, 3)                               # s == t
    # single fragment: no boundary at all (nb == 0)
    g1 = erdos_renyi(12, 30, seed=2)
    fr1 = fragment_graph(g1, np.zeros(12, np.int32), 1)
    sess1 = connect(fr1)
    pairs = [(0, 5), (5, 0), (2, 2)]
    got = sess1.run([Reach(s, t) for s, t in pairs])
    for (s, t), r in zip(pairs, got):
        assert r.answer == oracle_reach(g1, s, t)
    d = sess1.run([Dist(s, t) for s, t in pairs])
    for (s, t), r in zip(pairs, d):
        assert r.distance == oracle_dist(g1, s, t)


# ---------------------------------------------------------------------------
# serving loop
# ---------------------------------------------------------------------------

def test_query_server_matches_oracle_across_batches():
    g, fr = _case(36, 110, 4, 11)
    srv = QueryServer(fr, batch_size=8, start=False)
    rng = np.random.default_rng(0)
    pairs = [(int(rng.integers(g.n)), int(rng.integers(g.n)))
             for _ in range(19)]                       # odd: forces padding
    res = srv.serve_pairs(pairs)
    assert res == [oracle_reach(g, s, t) for s, t in pairs]
    assert srv.batches_run == 3

    for s, t in pairs[:5]:
        srv.submit(s, t, kind="dist")
    srv.submit(pairs[0][0], pairs[0][1], kind="bounded", bound=2)
    out = srv.flush()
    for r in out:
        want = oracle_dist(g, r.s, r.t)
        if r.kind == "dist":
            assert r.value == want
        else:
            assert r.value == (want is not None and want <= 2)


# ---------------------------------------------------------------------------
# the batched local stage: rows as lanes over the blocks' union edge list
# ---------------------------------------------------------------------------

LANE_K = 12        # more fragments than the smallest bucket, fewer than 16


@pytest.fixture(scope="module")
def lane_versions():
    """A 12-fragment graph warmed with both semirings, and the MVCC head
    after one delta that inserts edges and deletes others (a deletion
    swaps the fragment's last edge slot into the freed one, so the edge
    lists change order, not only content)."""
    from repro.core import GraphDelta
    from repro.core.versions import VersionedCacheStore
    g = erdos_renyi(160, 420, n_labels=3, seed=7)
    fr = fragment_graph(g, random_partition(g, LANE_K, 7), LANE_K,
                        reserve_boundary=16, reserve_edges=16,
                        reserve_stubs=16)
    sess = connect(fr, backend="vmap").warm(with_dist=True)
    store = VersionedCacheStore(sess, capacity=4)
    rng = np.random.default_rng(7)
    gone = rng.choice(len(g.src), size=12, replace=False)
    new = rng.integers(0, g.n, size=(12, 2))
    delta = GraphDelta(add_src=new[:, 0], add_dst=new[:, 1],
                       del_src=g.src[gone], del_dst=g.dst[gone])
    ver, _ = store.commit_delta(delta)
    assert ver.fr is not fr and ver.fr.rvset_cache.bl_dist is not None
    return {"base": fr, "written": ver.fr}


def _lane_rows(fr, bucket, rng):
    """``bucket`` query pairs, the first three sources in one fragment and
    the fourth a boundary in-node, and their kernel inputs, with the
    second row's source moved onto a virtual-node stub slot of its
    fragment and the last row a pad row (source slot ``n_max``)."""
    same = np.flatnonzero(fr.part == fr.part[0])[:3]
    srcs = np.concatenate([same, fr.bnodes[:1],
                           rng.integers(0, fr.g.n, bucket)])[:bucket]
    pairs = np.stack([srcs, rng.integers(0, fr.g.n, bucket)], 1)
    cache = get_rvset_cache(fr, with_dist=True)
    blocks, pos, s_slot, _, _ = _cache._batch_inputs(fr, cache, pairs)
    s_slot = np.array(s_slot)
    stubs = sorted(fr.stubs[int(fr.part[srcs[1]])].values())
    assert stubs, "the second source's fragment has no stub"
    s_slot[1] = stubs[0]
    s_slot[-1] = fr.n_max
    return pairs, blocks, pos, s_slot


@pytest.mark.parametrize("version", ["base", "written"])
@pytest.mark.parametrize("tropical", [False, True], ids=["reach", "dist"])
@pytest.mark.parametrize("bucket", [8, 16, 32, 64])
def test_lane_frontiers_equal_single_source(lane_versions, bucket, tropical,
                                            version):
    """Every row of the lane relaxation holds exactly its single-source
    fixpoint, with fewer rows than fragments (the batch's own blocks) and
    with more (all fragments); the batch answers equal the oracles, also
    after a delta that reordered the edge slots."""
    fr = lane_versions[version]
    rng = np.random.default_rng(bucket)
    pairs, blocks, pos, s_slot = _lane_rows(fr, bucket, rng)
    m = _cache.fragment_blocks(bucket, fr.k)
    assert (blocks is None) == (bucket >= fr.k)
    assert m == (fr.k if blocks is None else len(blocks))
    pos = np.asarray(pos)
    frag = pos if blocks is None else np.asarray(blocks)[pos]
    assert np.array_equal(frag, fr.part[pairs[:, 0]])
    arrs = get_rvset_cache(fr).arrays
    F = jax.jit(_cache._lane_frontiers,
                static_argnames=("n_max", "tropical"))(
        arrs["esrc"], arrs["edst"], blocks, pos, s_slot, n_max=fr.n_max,
        tropical=tropical)
    lanes = np.asarray(F).reshape(m, fr.n_max + 1, bucket)
    got = lanes[pos, :, np.arange(bucket)]                   # [N, n+1]
    single = engine.single_source_dist if tropical \
        else engine.single_source_reach
    want = jax.vmap(lambda es, ed, sl: single(es, ed, sl, n_max=fr.n_max))(
        arrs["esrc"][frag], arrs["edst"][frag], s_slot)
    assert np.array_equal(got, np.asarray(want))
    assert (got[-1] == (engine.INF if tropical else False)).all()  # pad row
    # the union is every block's edges, shifted to its slots, in
    # destination order (what the sorted row reduction relies on)
    es, ed = _cache._union_edges(arrs["esrc"], arrs["edst"], blocks,
                                 fr.n_max)
    ed = np.asarray(ed)
    assert (np.diff(ed) >= 0).all()
    off = (np.arange(m) * (fr.n_max + 1))[:, None]
    ids = np.arange(fr.k) if blocks is None else np.asarray(blocks)
    want_edges = sorted(zip((arrs["esrc"][ids] + off).ravel().tolist(),
                            (arrs["edst"][ids] + off).ravel().tolist()))
    assert sorted(zip(np.asarray(es).tolist(), ed.tolist())) == want_edges
    # the batch path itself, against the oracles on this version's graph
    g = fr.g
    if tropical:
        d = _cache.dis_dist_batch(fr, pairs)
        assert [None if x < 0 else int(x) for x in d] == [
            oracle_dist(g, int(s), int(t)) for s, t in pairs]
    else:
        assert list(_cache.dis_reach_batch(fr, pairs)) == [
            oracle_reach(g, int(s), int(t)) for s, t in pairs]
