"""The plain reference: reachability, shortest distance, bounded distance
and regular path queries over a directed node-labelled graph, answered by
breadth-first search on the host.

It imports nothing of the system under test.  Its regular expressions
follow the system's documented query semantics (paper Sec. 5.1): ``R ::=
eps | a | RR | R|R | R* | R+ | R? | .`` over node labels, and a path
``s -> v1 -> ... -> vk -> t`` matches when the labels of its interior
nodes ``v1 .. vk`` spell a word of ``R``; ``s == t`` matches when ``R``
accepts the empty word.  The automaton here is Thompson's construction,
simulated on sets of states (the system builds Glushkov's).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path

WILD = -1


class Nfa:
    """Thompson automaton of a regex over integer node labels."""

    def __init__(self, regex: str):
        self.eps: List[List[int]] = []
        self.moves: List[Tuple[int, int, int]] = []    # (from, label, to)
        self._toks = self._tokenize(regex)
        self._pos = 0
        self.start, self.accept = self._alt()
        if self._pos != len(self._toks):
            raise ValueError(f"trailing tokens in {regex!r}")
        self.closure = [self._close({q}) for q in range(len(self.eps))]

    @staticmethod
    def _tokenize(rx: str) -> List[str]:
        toks, i = [], 0
        while i < len(rx):
            c = rx[i]
            if c.isspace():
                i += 1
            elif c in "()|*+?.":
                toks.append(c)
                i += 1
            else:
                j = i
                while j < len(rx) and rx[j].isdigit():
                    j += 1
                if j == i:
                    raise ValueError(f"bad regex character {c!r} in {rx!r}")
                toks.append(rx[i:j])
                i = j
        return toks

    def _state(self) -> int:
        self.eps.append([])
        return len(self.eps) - 1

    def _peek(self):
        return self._toks[self._pos] if self._pos < len(self._toks) else None

    def _alt(self):
        a, b = self._cat()
        while self._peek() == "|":
            self._pos += 1
            c, d = self._cat()
            s, e = self._state(), self._state()
            self.eps[s] += [a, c]
            self.eps[b].append(e)
            self.eps[d].append(e)
            a, b = s, e
        return a, b

    def _cat(self):
        s = e = self._state()
        while self._peek() not in (None, ")", "|"):
            a, b = self._rep()
            self.eps[e].append(a)
            e = b
        return s, e

    def _rep(self):
        a, b = self._atom()
        while self._peek() in ("*", "+", "?"):
            op = self._toks[self._pos]
            self._pos += 1
            s, e = self._state(), self._state()
            self.eps[s].append(a)
            self.eps[b].append(e)
            if op in ("*", "?"):
                self.eps[s].append(e)
            if op in ("*", "+"):
                self.eps[b].append(a)
            a, b = s, e
        return a, b

    def _atom(self):
        tok = self._toks[self._pos]
        self._pos += 1
        if tok == "(":
            a, b = self._alt()
            if self._peek() != ")":
                raise ValueError("unbalanced parentheses")
            self._pos += 1
            return a, b
        s, e = self._state(), self._state()
        self.moves.append((s, WILD if tok == "." else int(tok), e))
        return s, e

    def _close(self, states) -> frozenset:
        out, todo = set(states), list(states)
        while todo:
            for r in self.eps[todo.pop()]:
                if r not in out:
                    out.add(r)
                    todo.append(r)
        return frozenset(out)

    @property
    def nullable(self) -> bool:
        return self.accept in self.closure[self.start]


class HostGraph:
    """The graph as a sparse adjacency matrix."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 labels: np.ndarray):
        self.n = n
        self.labels = labels
        ones = np.ones(src.size, dtype=np.int32)
        self.adj = sp.csr_matrix((ones, (src, dst)), shape=(n, n))
        self.adj.sum_duplicates()
        self.adj_t = self.adj.T.tocsr()

    def distances(self, sources) -> np.ndarray:
        """[len(sources), n] hop counts (inf where unreachable)."""
        return shortest_path(self.adj, method="D", directed=True,
                             unweighted=True, indices=np.asarray(sources))

    def rpq(self, s: int, t: int, nfa: Nfa) -> bool:
        if s == t:
            return nfa.nullable
        n = self.n
        preds_t = self.adj_t.indices[self.adj_t.indptr[t]:self.adj_t.indptr[t + 1]]
        start = nfa.closure[nfa.start]
        if nfa.accept in start and np.any(preds_t == s):
            return True
        Q = len(nfa.eps)
        seen = np.zeros((Q, n), dtype=bool)
        front = np.zeros((Q, n), dtype=bool)
        at_s = np.zeros(n, dtype=bool)
        at_s[s] = True
        succ_s = self.adj_t @ at_s.astype(np.int32) > 0
        for q0, lab, q1 in nfa.moves:
            if q0 in start:
                hit = succ_s if lab == WILD else succ_s & (self.labels == lab)
                for q in nfa.closure[q1]:
                    front[q] |= hit
        while front.any():
            front &= ~seen
            seen |= front
            if seen[nfa.accept, preds_t].any():
                return True
            nxt = np.zeros_like(front)
            for q0, lab, q1 in nfa.moves:
                if not front[q0].any():
                    continue
                succ = self.adj_t @ front[q0].astype(np.int32) > 0
                hit = succ if lab == WILD else succ & (self.labels == lab)
                for q in nfa.closure[q1]:
                    nxt[q] |= hit
            front = nxt
        return False


def answer(graph: HostGraph, dist_row: np.ndarray, kind: str, s: int, t: int,
           bound: Optional[int], nfa: Optional[Nfa]):
    """What a read must answer: bool for reach / bounded / rpq, the hop
    count or None for dist."""
    if kind == "rpq":
        return graph.rpq(s, t, nfa)
    d = dist_row[t]
    if kind == "reach":
        return bool(np.isfinite(d))
    if kind == "dist":
        return int(d) if np.isfinite(d) else None
    return bool(np.isfinite(d) and d <= bound)


def answer_all(n: int, src: np.ndarray, dst: np.ndarray, labels: np.ndarray,
               reads: List, regex: str) -> List[object]:
    """The answer to each of ``reads`` on the graph, in order."""
    nfa = Nfa(regex)
    graph = HostGraph(n, src, dst, labels)
    sources = sorted({r.s for r in reads if r.kind != "rpq"})
    rows = {}
    for lo in range(0, len(sources), 32):
        block = sources[lo:lo + 32]
        for s, row in zip(block, graph.distances(block)):
            rows[s] = row
    return [answer(graph, rows.get(r.s), r.kind, r.s, r.t, r.bound, nfa)
            for r in reads]
