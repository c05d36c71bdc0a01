"""Host oracles used by the test-suite (networkx + pure python)."""
from __future__ import annotations

from collections import deque

import networkx as nx

from repro.core.automaton import L_S, L_T, L_WILD


def nx_digraph(g):
    G = nx.DiGraph()
    G.add_nodes_from(range(g.n))
    G.add_edges_from(zip(g.src.tolist(), g.dst.tolist()))
    return G


class GraphOracle:
    """The three oracles over one networkx graph, built once: at a million
    nodes building the graph costs far more than answering one query.
    ``add_edges`` moves the oracle to a post-insertion snapshot in place."""

    def __init__(self, g):
        self.g = g
        self.G = nx_digraph(g)

    def add_edges(self, src, dst) -> None:
        self.G.add_edges_from(zip(list(map(int, src)), list(map(int, dst))))

    def reach(self, s, t) -> bool:
        return nx.has_path(self.G, s, t)

    def dist(self, s, t):
        try:
            return nx.shortest_path_length(self.G, s, t)
        except nx.NetworkXNoPath:
            return None

    def rpq(self, s, t, qa) -> bool:
        """Product-automaton BFS over (node, state)."""
        if s == t:
            return bool(qa.nullable)
        labels = self.g.labels

        def match(v, q):
            lq = qa.state_labels[q]
            if lq >= 0:
                return labels[v] == lq
            if lq == L_WILD:
                return True
            if lq == L_S:
                return v == s
            if lq == L_T:
                return v == t
            return False

        start = (s, 0)
        seen = {start}
        dq = deque([start])
        while dq:
            v, q = dq.popleft()
            for v2 in self.G.successors(v):
                for q2 in range(qa.n_states):
                    if qa.trans[q, q2] and match(v2, q2):
                        if v2 == t and q2 == qa.final:
                            return True
                        if (v2, q2) not in seen:
                            seen.add((v2, q2))
                            dq.append((v2, q2))
        return False


def oracle_reach(g, s, t) -> bool:
    return GraphOracle(g).reach(s, t)


def oracle_dist(g, s, t):
    return GraphOracle(g).dist(s, t)


def oracle_rpq(g, s, t, qa) -> bool:
    return GraphOracle(g).rpq(s, t, qa)
