"""Partial-evaluation engine: localEval + evalDG in pure JAX.

This is the paper's contribution (Sections 3-5), restructured for SPMD
hardware (see DESIGN.md Section 2):

* ``local_eval_reach``   — procedure localEval  (Fig. 3): per-fragment
  Boolean reachability from every owned in-node (and s) to every virtual
  node (and t), computed as *batched frontier propagation* over the
  fragment's padded edge list instead of per-source DFS.  One call == one
  site's partial answer; it never communicates.
* ``local_eval_dist``    — procedure localEval_d (Sec. 4): same, over the
  tropical (min, +) semiring, values clipped at the query bound.
* ``local_eval_regular`` — procedure localEval_r (Fig. 7): same, lifted to
  the product with the query automaton G_q(R).
* ``evaldg_reach / evaldg_dist`` — procedures evalDG / evalDG_d / evalDG_r:
  the coordinator's Boolean-equation-system solve, expressed as
  single-source fixpoint iteration on the dependency-graph matrix (or-and /
  min-plus vector-matrix products) — O(diam(G_f) * |V_f|^2) work.  evalDG_r
  reuses ``evaldg_reach`` on the (|V_f|*|Q|)-sized product matrix.

All functions are shape-static and jit/vmap/shard_map-compatible; the
fragment axis is mapped *outside* (``api.py`` uses vmap for single-host
evaluation, ``distributed.py`` uses shard_map across a device mesh).

Conventions (set up by ``fragments.fragment_graph``):
  * local node slots 0..n_max-1 are real nodes + virtual stubs; slot n_max is
    the pad node; pad edges self-loop on it; pad target columns point at it.
  * boundary rows/cols 0..B-3 are V_f in-nodes; row B-2 is s; col B-1 is t;
    row index B means "dropped" (scatter mode='drop').
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INF = np.int32(1 << 29)   # numpy: importing must not start a backend


class QueryStats(NamedTuple):
    """Measured guarantees (paper Theorems 1-3).

    Queries served inside a fused batch carry *group-amortized* stats
    (core.session): the group's ONE collective is split across its
    queries, so summing over any group yields exactly the wire size of
    that collective and one round — never N copies of it.
    """
    payload_bits: int        # rvset bits shipped (<= |V_f|^2 or |R|^2|V_f|^2;
                             # amortized share of the group wire when fused)
    collective_rounds: int   # visits per site (seed: 1; fused: 1 per group,
                             # stamped on the group's first query)
    boundary: int            # |V_f| + 2 query slots
    states: int              # |Q| (1 for plain/bounded reachability)


# ---------------------------------------------------------------------------
# local propagation primitives (one fragment; vmapped/shard_mapped outside)
# ---------------------------------------------------------------------------

def _propagate_bool(esrc, edst, frontier):
    """Fixpoint of frontier[v'] |= OR_{(v,v') in E} frontier[v].

    frontier: [S, n_max+1] bool.  Batched over S sources; iterates until no
    change (<= fragment diameter steps).
    """
    n_slots = frontier.shape[-1]

    def step(state):
        seen, _ = state
        msgs = jnp.take(seen, esrc, axis=1)                       # [S, E]
        agg = jax.ops.segment_max(msgs.T.astype(jnp.int8), edst,
                                  num_segments=n_slots)           # [n+1, S]
        new = seen | (agg.T > 0)
        return new, jnp.any(new != seen)

    # init flag derived from the (possibly device-varying) data so the carry
    # type matches under shard_map; all-False frontier needs no iterations.
    frontier, _ = jax.lax.while_loop(lambda st: st[1], step,
                                     (frontier, jnp.any(frontier)))
    return frontier


def _propagate_dist(esrc, edst, dist, cap):
    """Fixpoint of dist[v'] = min(dist[v'], min_{(v,v') in E} dist[v] + 1),
    entries above ``cap`` snapped to INF (paper Sec. 4 keeps dist < l only).
    """
    n_slots = dist.shape[-1]

    def step(state):
        d, _ = state
        msgs = jnp.take(d, esrc, axis=1) + 1                      # [S, E]
        agg = jax.ops.segment_min(msgs.T, edst, num_segments=n_slots)
        new = jnp.minimum(d, agg.T)
        new = jnp.where(new > cap, INF, new)
        return new, jnp.any(new != d)

    dist, _ = jax.lax.while_loop(lambda st: st[1], step,
                                 (dist, jnp.any(dist < INF)))
    return dist


def _with_query_source(src_local, src_row, s_local, n_max: int, B: int):
    """Fill the reserved last source slot with the query source s
    (active only in the fragment owning s; dropped elsewhere)."""
    s_row = jnp.where(s_local < n_max, jnp.int32(B - 2), jnp.int32(B))
    return src_local.at[-1].set(s_local), src_row.at[-1].set(s_row)


# ---------------------------------------------------------------------------
# query-independent frontiers (rvset cache phase; DESIGN.md Sec. 3)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_max",))
def local_frontier_reach(esrc, edst, src_local, *, n_max: int):
    """All-sources local fixpoint WITHOUT the query slots: frontier[j, v] = 1
    iff in-node source j reaches local slot v inside this fragment.

    This is the expensive part of localEval and depends only on the
    fragmentation, so ``core.cache`` computes it once per Fragmentation and
    reuses it for every subsequent query (amortized rvset).
    """
    S = src_local.shape[0]
    frontier = jnp.zeros((S, n_max + 1), dtype=bool)
    frontier = frontier.at[jnp.arange(S), src_local].set(True)
    frontier = frontier.at[:, n_max].set(False)
    return _propagate_bool(esrc, edst, frontier)


@functools.partial(jax.jit, static_argnames=("n_max",))
def local_frontier_dist(esrc, edst, src_local, *, n_max: int):
    """Tropical counterpart of :func:`local_frontier_reach` (uncapped; the
    per-query bound is applied at answer time, which is equivalent for
    shortest distances)."""
    S = src_local.shape[0]
    dist = jnp.full((S, n_max + 1), INF, dtype=jnp.int32)
    dist = dist.at[jnp.arange(S), src_local].min(0)
    dist = dist.at[:, n_max].set(INF)
    return _propagate_dist(esrc, edst, dist, INF)


@functools.partial(jax.jit, static_argnames=("n_max",))
def resume_frontier_reach(esrc, edst, frontier, *, n_max: int):
    """Continue a Boolean all-sources fixpoint from a warm state.

    Used by incremental cache repair (DESIGN.md Sec. 3.5): after edge
    *insertions* the old converged frontier is a valid under-approximation,
    so re-running the fixpoint from it converges in O(new-path length)
    relaxations instead of O(diam).  ``frontier``: [S, n_max+1] bool with
    each row's own source bit already set."""
    frontier = frontier.at[:, n_max].set(False)
    return _propagate_bool(esrc, edst, frontier)


@functools.partial(jax.jit, static_argnames=("n_max",))
def resume_frontier_dist(esrc, edst, dist, *, n_max: int):
    """Tropical twin of :func:`resume_frontier_reach`: the old distances
    are realizable upper bounds after insertions, so relaxation from them
    converges to the new exact distances."""
    dist = dist.at[:, n_max].set(INF)
    return _propagate_dist(esrc, edst, dist, INF)


# ---------------------------------------------------------------------------
# per-query propagation (cheap phase against the cache; DESIGN.md Sec. 3)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_max",))
def single_source_reach(esrc, edst, src, *, n_max: int):
    """One-source Boolean fixpoint on one fragment: frontier [n_max+1] bool.
    ``src == n_max`` (pad) yields the all-false frontier.  vmap the leading
    axis of all three args for the batched multi-query path (each query
    propagates over its own fragment's edge list)."""
    frontier = jnp.zeros((1, n_max + 1), dtype=bool)
    frontier = frontier.at[0, src].set(src < n_max)
    frontier = frontier.at[0, n_max].set(False)
    return _propagate_bool(esrc, edst, frontier)[0]


@functools.partial(jax.jit, static_argnames=("n_max",))
def single_source_dist(esrc, edst, src, *, n_max: int):
    """One-source tropical fixpoint: dist [n_max+1] int32 (INF absent)."""
    dist = jnp.full((1, n_max + 1), INF, dtype=jnp.int32)
    dist = dist.at[0, src].min(jnp.where(src < n_max, 0, INF))
    dist = dist.at[0, n_max].set(INF)
    return _propagate_dist(esrc, edst, dist, INF)[0]


@functools.partial(jax.jit, static_argnames=("n_max",))
def single_source_regular(esrc, edst, labels, gids, q_labels, q_trans,
                          s_slot, q_start, s_gid, t_gid, *, n_max: int):
    """Per-query product-automaton forward fixpoint from (s, u_s) on s's
    fragment: f [n_max+1, Q] bool — f[v, q] = 1 iff a path from s occupying
    the start state reaches local slot v in state q (every step matching)."""
    Q = q_labels.shape[0]
    match = _match_matrix(labels, gids, q_labels, s_gid, t_gid)
    match = match.at[n_max, :].set(False)                     # [n+1, Q]
    f = jnp.zeros((n_max + 1, Q), dtype=bool)
    f = f.at[s_slot, q_start].set((s_slot < n_max) & match[s_slot, q_start])
    # int32 accumulator: an int8 dot wraps once >=128 predecessor states
    # are simultaneously active (wide alternations)
    tf = q_trans.astype(jnp.int32)

    def step(state):
        cur, _ = state
        # advance the automaton, then push along fragment edges
        adv = (cur.astype(jnp.int32) @ tf) > 0                # [n+1, Q]
        msgs = adv[esrc].astype(jnp.int8)                     # [E, Q]
        agg = jax.ops.segment_max(msgs, edst, num_segments=n_max + 1)
        new = cur | ((agg > 0) & match)
        return new, jnp.any(new != cur)

    f, _ = jax.lax.while_loop(lambda st: st[1], step, (f, jnp.any(f)))
    return f


@functools.partial(jax.jit, static_argnames=("n_max",))
def reverse_target_regular(esrc, edst, labels, gids, q_labels, q_trans,
                           t_slot, s_gid, t_gid, *, n_max: int):
    """Per-query product-automaton BACKWARD fixpoint to (t, u_t) on one
    fragment: r [n_max+1, Q] bool — r[v, q] = 1 iff from local slot v
    occupying state q a local path reaches t (or the stub of t) in the
    accepting state, with every step's target matching its state.

    vmapped over all fragments this yields the t-column of the dependency
    matrix without any all-sources work (DESIGN.md Sec. 3.2)."""
    Q = q_labels.shape[0]
    match = _match_matrix(labels, gids, q_labels, s_gid, t_gid)
    match = match.at[n_max, :].set(False)
    r = jnp.zeros((n_max + 1, Q), dtype=bool)
    r = r.at[t_slot, Q - 1].set((t_slot < n_max) & match[t_slot, Q - 1])
    tf = q_trans.astype(jnp.int32)          # int32: see single_source_regular

    def step(state):
        cur, _ = state
        ok = (cur & match).astype(jnp.int8)                   # [n+1, Q']
        msgs = ok[edst]                                       # [E, Q']
        agg = jax.ops.segment_max(msgs, esrc,
                                  num_segments=n_max + 1)     # [n+1, Q']
        pre = ((agg > 0).astype(jnp.int32) @ tf.T) > 0        # [n+1, Q]
        new = cur | pre
        return new, jnp.any(new != cur)

    r, _ = jax.lax.while_loop(lambda st: st[1], step, (r, jnp.any(r)))
    return r


# ---------------------------------------------------------------------------
# localEval: plain reachability (paper Fig. 3, procedure localEval)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_max", "B"))
def local_eval_reach(esrc, edst, src_local, src_row, tgt_local,
                     s_local, t_local, *, n_max: int, B: int):
    """One fragment's rvset, as a row block of the dependency matrix.

    Returns Rloc [B, B] bool: Rloc[row(v), col(w)] = 1 iff source v (owned
    in-node, or s) reaches virtual node w (or t) inside this fragment.  Rows
    owned by other fragments stay all-false, so assembly is elementwise OR —
    a single collective (the paper's "each site is visited only once").
    """
    src_local, src_row = _with_query_source(src_local, src_row, s_local,
                                            n_max, B)
    S = src_local.shape[0]
    frontier = jnp.zeros((S, n_max + 1), dtype=bool)
    frontier = frontier.at[jnp.arange(S), src_local].set(True)
    frontier = frontier.at[:, n_max].set(False)       # pad node never seen
    frontier = _propagate_bool(esrc, edst, frontier)

    # read out virtual-node columns (+ t column) for each source row
    cols = jnp.concatenate([tgt_local[: B - 2],
                            jnp.array([n_max], jnp.int32),      # s col unused
                            t_local[None].astype(jnp.int32)])
    out = jnp.take(frontier, cols, axis=1)            # [S, B]
    out = out & (cols[None, :] < n_max + 1) & (cols[None, :] != n_max)
    rloc = jnp.zeros((B, B), dtype=bool)
    rloc = rloc.at[src_row].max(out, mode="drop")
    return rloc


# ---------------------------------------------------------------------------
# localEval_d: bounded reachability (paper Sec. 4)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_max", "B"))
def local_eval_dist(esrc, edst, src_local, src_row, tgt_local,
                    s_local, t_local, cap, *, n_max: int, B: int):
    """Tropical rvset: Wloc[row(v), col(w)] = local dist(v, w) (INF absent)."""
    src_local, src_row = _with_query_source(src_local, src_row, s_local,
                                            n_max, B)
    S = src_local.shape[0]
    dist = jnp.full((S, n_max + 1), INF, dtype=jnp.int32)
    dist = dist.at[jnp.arange(S), src_local].min(0)
    dist = dist.at[:, n_max].set(INF)
    dist = _propagate_dist(esrc, edst, dist, cap)

    cols = jnp.concatenate([tgt_local[: B - 2],
                            jnp.array([n_max], jnp.int32),
                            t_local[None].astype(jnp.int32)])
    out = jnp.take(dist, cols, axis=1)
    out = jnp.where((cols[None, :] == n_max), INF, out)
    wloc = jnp.full((B, B), INF, dtype=jnp.int32)
    wloc = wloc.at[src_row].min(out, mode="drop")
    return wloc


# ---------------------------------------------------------------------------
# localEval_r: regular reachability (paper Fig. 7)
# ---------------------------------------------------------------------------

def _match_matrix(labels, gids, q_labels, s_gid, t_gid):
    """match[v, q]: node in local slot v can occupy automaton state q.

    q_labels sentinels: >=0 symbol, -1 only-s, -2 only-t, -3 wildcard.
    Pad slots (labels -9 / gids -1) match nothing.
    """
    lv = labels[:, None]
    gv = gids[:, None]
    lq = q_labels[None, :]
    return ((lq >= 0) & (lv == lq)) | \
           ((lq == -3) & (lv >= 0)) | \
           ((lq == -1) & (gv == s_gid)) | \
           ((lq == -2) & (gv == t_gid))


@functools.partial(jax.jit, static_argnames=("n_max", "B"))
def local_eval_regular(esrc, edst, src_local, src_row, tgt_local,
                       labels, gids, q_labels, q_trans,
                       s_local, t_local, s_gid, t_gid, *,
                       n_max: int, B: int):
    """Product-automaton rvset: Rloc [(B*Q), (B*Q)] bool.

    Row (v, q0): the source pair "in-node v occupying state q0"; column
    (w, q'): "path leaves this fragment arriving at virtual node w in state
    q'" (or arrives at t in q').  Equivalent to the paper's vectors of
    Boolean formulas v.rvec[u] over variables X_(w,u').
    """
    Q = q_labels.shape[0]
    src_local, src_row = _with_query_source(src_local, src_row, s_local,
                                            n_max, B)
    S = src_local.shape[0]
    match = _match_matrix(labels, gids, q_labels, s_gid, t_gid)  # [n+1, Q]
    match = match.at[n_max, :].set(False)

    # frontier[j, q0, v, q]: from source pair (src j, state q0) one can reach
    # local slot v occupying state q (all label constraints satisfied).
    src_match = match[src_local, :]                              # [S, Q]
    eye = jnp.eye(Q, dtype=bool)
    frontier = jnp.zeros((S, Q, n_max + 1, Q), dtype=bool)
    frontier = frontier.at[jnp.arange(S)[:, None, None],
                           jnp.arange(Q)[None, :, None],
                           src_local[:, None, None],
                           jnp.arange(Q)[None, None, :]].max(
        (src_match[:, :, None] & eye[None, :, :]))
    frontier = frontier.at[:, :, n_max, :].set(False)

    tf = q_trans.astype(jnp.int32)          # int32: int8 wraps at >=128
                                            # simultaneously-active states

    def step(state):
        f, _ = state
        # advance automaton: f2[j,q0,v,q'] = OR_q f[j,q0,v,q] & trans[q,q']
        f2 = (jnp.einsum("sqnp,pr->sqnr", f.astype(jnp.int32), tf) > 0)
        msgs = jnp.take(f2, esrc, axis=2)                        # [S,Q,E,Q]
        msgs = jnp.moveaxis(msgs, 2, 0).astype(jnp.int8)         # [E,S,Q,Q]
        agg = jax.ops.segment_max(msgs, edst, num_segments=n_max + 1)
        agg = jnp.moveaxis(agg > 0, 0, 2)                        # [S,Q,n+1,Q]
        new = f | (agg & match[None, None, :, :])
        return new, jnp.any(new != f)

    frontier, _ = jax.lax.while_loop(lambda st: st[1], step,
                                     (frontier, jnp.any(frontier)))

    cols = jnp.concatenate([tgt_local[: B - 2],
                            jnp.array([n_max], jnp.int32),
                            t_local[None].astype(jnp.int32)])
    out = jnp.take(frontier, cols, axis=2)                       # [S,Q,B,Q]
    out = out & (cols[None, None, :, None] != n_max)
    out = out.reshape(S, Q, B * Q)

    rows = src_row[:, None] * Q + jnp.arange(Q)[None, :]         # [S, Q]
    rows = jnp.where(src_row[:, None] >= B, B * Q, rows)         # drop pads
    rloc = jnp.zeros((B * Q, B * Q), dtype=bool)
    rloc = rloc.at[rows.reshape(-1)].max(out.reshape(S * Q, B * Q),
                                         mode="drop")
    return rloc


# ---------------------------------------------------------------------------
# evalDG: assembling at the coordinator (paper Fig. 4 / Secs. 4-5)
# ---------------------------------------------------------------------------

def evaldg_reach(D, src_rows, tgt_cols):
    """Single-source fixpoint on the dependency matrix D [B, B] bool.

    x := x OR x@D until fixpoint (<= diam(G_f) or-and vector-matrix
    products, each dispatched to the Pallas MXU kernel on TPU); answer:
    any reachable column in ``tgt_cols``.  src_rows / tgt_cols: masks [B].
    """
    from ..kernels.bool_matmul.ops import or_and_matmul
    # seed the carry from D so its device-varying type matches the body's
    x0 = src_rows | (D[0] & False)

    def step(state):
        x, _ = state
        nxt = x | or_and_matmul(x[None, :], D)[0]
        return nxt, jnp.any(nxt != x)

    x, _ = jax.lax.while_loop(lambda st: st[1], step, (x0, jnp.any(x0)))
    return jnp.any(x & tgt_cols)


def evaldg_dist(W, src_rows, tgt_cols):
    """Single-source tropical fixpoint (Bellman-Ford on G_d; the paper uses
    Dijkstra — Bellman-Ford is the parallel-matrix equivalent).  The
    vector-matrix relax rides the Pallas tropical kernel on TPU.
    Returns min distance onto ``tgt_cols`` (INF if unreachable)."""
    from ..kernels.tropical_matmul.ops import min_plus_matmul
    d0 = jnp.where(src_rows, 0, INF).astype(jnp.int32) + (W[0] & 0)

    def step(state):
        d, _ = state
        relax = min_plus_matmul(d[None, :], W)[0]
        nxt = jnp.minimum(d, relax)
        nxt = jnp.minimum(nxt, INF)
        return nxt, jnp.any(nxt != d)

    d, _ = jax.lax.while_loop(lambda st: st[1], step,
                              (d0, jnp.any(d0 < INF)))
    return jnp.min(jnp.where(tgt_cols, d, INF))
