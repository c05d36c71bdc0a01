"""The benchmark's inputs, made from ``--seed`` alone: the graph of a
configuration and the requests and writes of a traffic mix.

Everything here is numpy and imports nothing of the system under test, so
the plain reference (``bench/reference.py``) can take the same arrays.

Every seed gets the same amount of work: the graph's structure and the
set of reads are drawn once from the configuration's ``structure_seed``,
and ``--seed`` relabels them and sets their order.

Graph (``kind: er_blocks``): ``blocks`` Erdos-Renyi blocks of
``block_nodes`` nodes and ``degree * block_nodes`` edges each, joined by
``cross_edges`` uniform edges over the whole graph, node labels uniform
over ``labels`` values, partitioned along the blocks.  A run's graph is
that structure with its blocks, the nodes within each block and the edge
list put in an order drawn from the seed: isomorphic for every seed, with
the same fragments up to their order.

Reads come in the traffic mix's ``kinds`` (of reach, exact dist, bounded
dist and RPQ), an equal share each, in a random order within every run of
kinds.  Half have uniform endpoints and half end a short random walk from
their source (an RPQ walk stays on nodes whose label starts the regex's
star, so that such pairs can answer true).  Each kind's reads are a pool
of ``pool`` reads drawn on the structure, mapped onto the run's node ids
and served in an order drawn from the seed, again in a new order each
time the pool runs out.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

KINDS = ("reach", "dist", "bounded", "rpq")


@dataclasses.dataclass
class Graph:
    n: int
    src: np.ndarray        # int64 [m]
    dst: np.ndarray        # int64 [m]
    labels: np.ndarray     # int32 [n]
    part: np.ndarray       # int32 [n] fragment of each node
    k: int
    # the structure this graph relabels, and the id here of each of its
    # nodes (None: the graph is a structure itself)
    base: Optional["Graph"] = None
    new_id: Optional[np.ndarray] = None
    structure_seed: int = 0

    @property
    def m(self) -> int:
        return int(self.src.size)


def build_structure(gcfg: dict) -> Graph:
    """The configuration's graph before relabeling, the same for every
    seed (see the module docstring)."""
    if gcfg["graph"] != "er_blocks":
        raise ValueError(f"unknown graph kind {gcfg['graph']!r}")
    k, block = int(gcfg["blocks"]), int(gcfg["block_nodes"])
    per = int(gcfg["degree"]) * block
    n = k * block
    sseed = int(gcfg["structure_seed"])
    rng = np.random.default_rng([sseed, 0])
    base = np.repeat(np.arange(k, dtype=np.int64) * block, per)
    src = np.concatenate([base + rng.integers(0, block, k * per),
                          rng.integers(0, n, int(gcfg["cross_edges"]))])
    dst = np.concatenate([base + rng.integers(0, block, k * per),
                          rng.integers(0, n, int(gcfg["cross_edges"]))])
    labels = rng.integers(0, int(gcfg["labels"]), n).astype(np.int32)
    part = (np.arange(n) // block).astype(np.int32)
    return Graph(n, src.astype(np.int64), dst.astype(np.int64), labels,
                 part, k, structure_seed=sseed)


def build_graph(gcfg: dict, seed: int) -> Graph:
    """The configuration's structure relabeled by ``seed``: blocks, the
    nodes within each block and the edge list in an order drawn from the
    seed.  Block ``b`` of the structure becomes fragment ``order[b]``."""
    s = build_structure(gcfg)
    block = s.n // s.k
    rng = np.random.default_rng([seed, 0])
    order = rng.permutation(s.k).astype(np.int64)
    within = rng.permuted(np.tile(np.arange(block, dtype=np.int64),
                                  (s.k, 1)), axis=1)
    new_id = (order[:, None] * block + within).reshape(-1)
    labels = np.empty_like(s.labels)
    labels[new_id] = s.labels
    e = rng.permutation(s.m)
    return Graph(s.n, new_id[s.src][e], new_id[s.dst][e], labels,
                 (np.arange(s.n) // block).astype(np.int32), s.k,
                 base=s, new_id=new_id, structure_seed=s.structure_seed)


def fragment_needs(g: Graph) -> dict:
    """What the block fragmentation of ``g`` fills of each padded capacity
    before any headroom: boundary nodes (in-nodes of crossing edges), local
    slots (own nodes plus stubs), edge slots and source rows, each the
    largest over the fragments."""
    ps, pd = g.part[g.src], g.part[g.dst]
    cross = ps != pd
    bnodes = np.unique(g.dst[cross])
    # stubs: distinct crossing targets per source fragment
    stub = np.unique(ps[cross].astype(np.int64) * g.n + g.dst[cross])
    stubs = np.bincount(stub // g.n, minlength=g.k)
    own = np.bincount(g.part, minlength=g.k)
    edges = np.bincount(ps, minlength=g.k)
    sources = np.bincount(g.part[bnodes], minlength=g.k)
    return dict(nb=int(bnodes.size), n_max=int((own + stubs).max()),
                e_max=int(edges.max()), s_max=int(sources.max()) + 1)


def reserves_for(g: Graph, caps: dict, pad_multiple: int) -> dict:
    """``fragment_graph`` headroom that lands every padded capacity exactly
    on the configuration's ``caps``, so that every seed compiles the same
    programs.  Raises when a seed's graph does not fit them."""
    need = fragment_needs(g)
    for key in ("n_max", "e_max"):
        if caps[key] % pad_multiple:
            raise ValueError(f"capacity {key}={caps[key]} is not a multiple "
                             f"of pad_multiple={pad_multiple}")
    res = dict(reserve_boundary=caps["nb"] - need["nb"],
               reserve_stubs=caps["n_max"] - need["n_max"],
               reserve_edges=caps["e_max"] - need["e_max"],
               reserve_sources=caps["s_max"] - need["s_max"])
    short = {k: v for k, v in res.items() if v < caps["min_headroom"]}
    if short:
        raise ValueError(f"graph needs {need}; capacities {caps} leave "
                         f"less than {caps['min_headroom']} headroom: {short}")
    return res


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Read:
    kind: str
    s: int
    t: int
    bound: Optional[int] = None


def csr(n: int, src: np.ndarray, dst: np.ndarray):
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst[order]


def walk(adj, labels, rng, s: int, steps: int, via_label=None) -> int:
    """End of a random walk of at most ``steps`` edges from ``s``; with
    ``via_label`` every node strictly inside the walk carries that label."""
    indptr, indices = adj
    v = s
    for i in range(steps):
        if i and via_label is not None and labels[v] != via_label:
            break
        succ = indices[indptr[v]:indptr[v + 1]]
        if succ.size == 0:
            break
        v = int(succ[rng.integers(succ.size)])
    return v


def draw_pool(g: Graph, adj, tr: dict, rng, kind: str,
              count: int) -> List[Read]:
    """``count`` reads of ``kind`` on ``g``: walk endpoints and uniform
    ones in a random order within every run of four."""
    w = int(round(tr["walk_share"] * 4))
    via = tr["regex_label"] if kind == "rpq" else None
    lo, hi = tr["walk_steps"]
    modes: List[bool] = []              # True: walk endpoint
    out = []
    for _ in range(count):
        if not modes:
            modes = list(rng.permutation([True] * w + [False] * (4 - w)))
        s = int(rng.integers(g.n))
        t = (walk(adj, g.labels, rng, s, int(rng.integers(lo, hi + 1)),
                  via_label=via)
             if modes.pop() else int(rng.integers(g.n)))
        bound = None
        if kind == "bounded":
            blo, bhi = tr["bound"]
            bound = int(rng.integers(blo, bhi + 1))
        out.append(Read(kind, s, int(t), bound))
    return out


class ReadMaker:
    """Serves reads one at a time from the traffic mix ``tr`` (see the
    module docstring): each kind's pool, drawn on ``g``'s structure from
    ``stream``, in an order drawn from ``rng``."""

    def __init__(self, g: Graph, tr: dict, rng, stream: int = 1):
        self.tr, self.rng = tr, rng
        base = g.base if g.base is not None else g
        new_id = g.new_id if g.new_id is not None else np.arange(g.n)
        prng = np.random.default_rng([g.structure_seed, stream])
        adj = csr(base.n, base.src, base.dst)
        self.pools = {
            kind: [Read(r.kind, int(new_id[r.s]), int(new_id[r.t]), r.bound)
                   for r in draw_pool(base, adj, tr, prng, kind,
                                      int(tr["pool"]))]
            for kind in tr["kinds"]}
        self.queues = {kind: [] for kind in tr["kinds"]}
        self.kinds: List[str] = []

    def next(self) -> Read:
        tr, rng = self.tr, self.rng
        if not self.kinds:
            self.kinds = [tr["kinds"][i]
                          for i in rng.permutation(len(tr["kinds"]))]
        kind = self.kinds.pop()
        queue, pool = self.queues[kind], self.pools[kind]
        if not queue:
            queue.extend(pool[i] for i in rng.permutation(len(pool)))
        return queue.pop()


def warm_reads(g: Graph, tr: dict, seed: int, count: int) -> List[Read]:
    """Reads for set-up, from pools of their own (the window's stay as
    they are whatever set-up draws)."""
    maker = ReadMaker(g, tr, np.random.default_rng([seed, 2]), stream=2)
    return [maker.next() for _ in range(count)]

