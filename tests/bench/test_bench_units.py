"""Units of the benchmark's yardstick: fixed capacities, and the plain
reference against the test-suite's networkx oracles."""
import json
import os

import numpy as np
import pytest

from bench_util import ROOT

from bench import reference
from bench import workload as wl


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config", ["er16x16k"])
def test_capacities_fixed_across_seeds(config):
    """Three seeds' full-size graphs fragment onto the configuration's
    padded capacities exactly, so every seed runs the same programs."""
    from repro.core import fragment_graph
    from repro.graph import Graph
    cfg = _config(config)
    seen = set()
    for seed in (3, 2**31 + 5, 3_000_000_019):
        g = wl.build_graph(cfg, seed)
        fr = fragment_graph(Graph(g.n, g.src, g.dst, g.labels), g.part, g.k,
                            pad_multiple=cfg["pad_multiple"],
                            **wl.reserves_for(g, cfg["capacities"],
                                              cfg["pad_multiple"]))
        seen.add((fr.n_boundary, fr.n_max, fr.e_max, fr.s_max, fr.B))
    caps = cfg["capacities"]
    assert seen == {(caps["nb"], caps["n_max"], caps["e_max"], caps["s_max"],
                     caps["nb"] + 2)}


def test_seeds_relabel_one_structure():
    """Two seeds serve the same graph and the same reads under other node
    ids and in another order: the work does not follow the seed."""
    cfg = _config("er16x16k")
    tr = dict(kinds=["reach", "dist", "bounded"], pool=64, walk_share=0.5,
              walk_steps=[1, 6], bound=[2, 15], regex_label=0)
    a, b = wl.build_graph(cfg, 3), wl.build_graph(cfg, 2**31 + 5)
    assert not np.array_equal(a.src, b.src)
    assert wl.fragment_needs(a) == wl.fragment_needs(b)
    s = a.base
    for g in (a, b):
        edges = set(zip(g.src.tolist(), g.dst.tolist()))
        assert edges == set(zip(g.new_id[s.src].tolist(),
                                g.new_id[s.dst].tolist()))
        assert np.array_equal(g.labels[g.new_id], s.labels)
        # each structure block is one fragment of the run
        frag = g.part[g.new_id].reshape(s.k, -1)
        assert (frag == frag[:, :1]).all()
    served = []
    for g, seed in ((a, 3), (b, 2**31 + 5)):
        back = np.argsort(g.new_id)
        maker = wl.ReadMaker(g, tr, np.random.default_rng([seed, 1]))
        reads = [maker.next() for _ in range(3 * 64)]
        served.append([(r.kind, int(back[r.s]), int(back[r.t]), r.bound)
                       for r in reads])
    assert sorted(served[0]) == sorted(served[1])
    assert served[0] != served[1]


def test_reserves_refuse_a_graph_that_does_not_fit():
    cfg = _config("er16x16k")
    g = wl.build_graph(cfg, 1)
    with pytest.raises(ValueError, match="headroom"):
        wl.reserves_for(g, dict(cfg["capacities"], nb=3000), 8)


@pytest.mark.parametrize("regex", ["0*", "(0|1)*2", "0+1?", ".*", "1 0* 2",
                                   "(0 1)|2*"])
def test_reference_matches_oracles(regex):
    """Reach, dist, bounded and RPQ answers of the reference equal the
    networkx oracles on a random labelled graph."""
    from oracles import GraphOracle
    from repro.core import build_query_automaton
    rng = np.random.default_rng(7)
    n = 60
    src, dst = rng.integers(0, n, 150), rng.integers(0, n, 150)
    labels = rng.integers(0, 3, n).astype(np.int32)
    reads = []
    for i in range(160):
        kind = wl.KINDS[i % 4]
        reads.append(wl.Read(kind, int(rng.integers(n)), int(rng.integers(n)),
                             int(rng.integers(1, 5)) if kind == "bounded"
                             else None))
    got = reference.answer_all(n, src, dst, labels, reads, regex)
    qa = build_query_automaton(regex, int)
    oracle = GraphOracle(wl.Graph(n, src, dst, labels, np.zeros(n, np.int32),
                                  1))
    for r, ans in zip(reads, got):
        if r.kind == "rpq":
            want = oracle.rpq(r.s, r.t, qa)
        elif r.kind == "reach":
            want = oracle.reach(r.s, r.t)
        else:
            d = oracle.dist(r.s, r.t)
            want = d if r.kind == "dist" else (d is not None
                                               and d <= r.bound)
        assert ans == want, (regex, r)
