"""Amortized rvset cache + batched multi-query engine (DESIGN.md Sec. 3).

The paper's guarantees are per-query, but a serving engine answers many
queries against the *same* fragmentation.  ``localEval`` splits cleanly:

* **query-independent phase** (expensive, once per Fragmentation):
  every fragment's all-sources local fixpoint — from each owned in-node to
  every local slot — assembled into the boundary-to-boundary dependency
  matrix ``D0 [|V_f|, |V_f|]`` and closed by repeated squaring
  (``bes.bool_closure`` / ``tropical_closure``: ceil(log2 |V_f|) semiring
  matmuls, the Pallas MXU kernels on TPU) instead of diam(G_f) relaxations
  per query;
* **per-query phase** (cheap): one single-source propagation from ``s`` in
  its own fragment, a pure gather of the ``t``-column out of the cached
  frontiers, and one or-and vector-matrix product through the closure.

Correctness identity (checked property-style in tests/test_batched_cache.py):

    reach(s, t) = direct(s, t)                                  # local path
                | OR_{u,v in V_f}  sb[u] & C[u, v] & tc[v]

where ``sb[u]`` = s locally reaches the stub of boundary node u, ``C`` is
the reflexive-transitive closure of D0, and ``tc[v]`` = in-node v locally
reaches t (gathered from the cached frontier of v's fragment — virtual-stub
slots included, so cross-edge arrivals at a boundary t need no special
aliasing).  The tropical and product-automaton variants replace (OR, AND)
with (min, +) and the state-expanded matrix respectively.

Batched: ``dis_reach_batch(fr, pairs)`` answers N pairs in ONE jitted call —
N single-source propagations, relaxed as the lanes of one fixpoint over
their fragments' shared edge list, + one [N, |V_f|] x [|V_f|, |V_f|]
or-and matmul against the cached closure.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from . import bes, engine
from .automaton import QueryAutomaton
from .engine import INF
from .fragments import Fragmentation

NO_NODE = np.int32(-(2 ** 30))     # gid that matches no L_S / L_T state


# ---------------------------------------------------------------------------
# cache container + construction
# ---------------------------------------------------------------------------

MAX_RPQ_CLOSURES = 32      # LRU-evicted: each is an [(nb*Q), (nb*Q)] matrix


@dataclasses.dataclass
class RvsetCache:
    """Query-independent closures + frontiers for one Fragmentation."""

    fr: Fragmentation
    arrays: Dict[str, jax.Array]      # fr.arrays uploaded once to device
    bl_frontier: jax.Array            # [nb, n_max+1] bool, in-node -> slot
    closure: jax.Array                # [nb, nb] bool, reflexive-transitive
    part_b: np.ndarray                # [nb] owning fragment of boundary node
    bl_dist: Optional[jax.Array] = None       # [nb, n_max+1] int32
    dist_closure: Optional[jax.Array] = None  # [nb, nb] int32, diag 0
    rpq_closures: Dict[Tuple, jax.Array] = dataclasses.field(
        default_factory=dict)         # automaton key -> [(nb*Q), (nb*Q)]
    # incremental-maintenance state (core.incremental; DESIGN.md Sec. 3.5)
    version: int = 0                  # bumped on every repair/recompute
    repair_debt: float = 0.0          # deletion-recompute cost accumulator

    @property
    def nb(self) -> int:
        return self.fr.n_boundary

    def refresh_device_arrays(self, touched=None) -> None:
        """Re-upload the (host-mutated) fragment arrays after a delta; the
        cached rpq closures are dropped (they bake in the old arrays) and
        rebuild lazily on the next regular query.

        ``touched`` names the subset of ``fr.arrays`` keys the delta
        actually mutated (``incremental.touched_arrays``); only those are
        re-uploaded and the rest keep their device buffers — the
        device-side half of the copy-on-write story that lets MVCC
        versions share untouched buffers (``None`` re-uploads everything).
        A *new* dict is always bound so cache clones sharing the old dict
        (``core.versions``) never observe the refresh.

        ``jnp.array`` (copy=True), NOT ``jnp.asarray``: on CPU the latter
        may zero-copy alias the host buffer, and these host arrays are
        mutated in place by ``Fragmentation.apply_delta`` — an aliased
        device array would see mid-update state and survive a rollback."""
        names = self.fr.arrays.keys() if touched is None else touched
        arrays = dict(self.arrays)
        for k in names:
            arrays[k] = jnp.array(self.fr.arrays[k])
        self.arrays = arrays
        self.part_b = self.fr.boundary_owner()
        self.rpq_closures.clear()
        self.version += 1

    # -- rollback snapshots (failed-delta recovery; DESIGN.md Sec. 7) ------

    _SNAP_FIELDS = ("arrays", "bl_frontier", "closure", "part_b", "bl_dist",
                    "dist_closure", "rpq_closures", "version", "repair_debt")

    def snapshot(self) -> dict:
        """Shallow state capture for rollback: repairs rebind immutable
        jax arrays (functional ``.at[].set``), so references suffice —
        except ``rpq_closures``, which repairs clear *in place*."""
        snap = {name: getattr(self, name) for name in self._SNAP_FIELDS}
        snap["rpq_closures"] = dict(self.rpq_closures)
        return snap

    def restore(self, snap: dict) -> None:
        for name in self._SNAP_FIELDS:
            setattr(self, name, snap[name])
        self.rpq_closures = dict(snap["rpq_closures"])


def _boundary_rows(fr: Fragmentation, frontiers, fill, combine):
    """Scatter stacked per-fragment source rows [k, S, n+1] into one
    [nb, n+1] matrix indexed by boundary position (each in-node is owned by
    exactly one fragment, so rows never collide)."""
    B = fr.B
    src_row = fr.arrays["src_row"]                  # [k, S]; pad rows == B
    flat_rows = jnp.array(src_row.reshape(-1))
    flat = frontiers.reshape(-1, frontiers.shape[-1])
    out = jnp.full((B + 1, frontiers.shape[-1]), fill, frontiers.dtype)
    out = combine(out.at[flat_rows], flat)
    return out[: fr.n_boundary]


def prepare_rvset_cache(fr: Fragmentation,
                        with_dist: bool = False) -> RvsetCache:
    """Build (or extend) the amortized cache and attach it to ``fr``."""
    cache = fr.rvset_cache
    if cache is None:
        # jnp.array (copy=True), not asarray: see refresh_device_arrays.
        arrs = {k: jnp.array(v) for k, v in fr.arrays.items()}
        front = _each_fragment(engine.local_frontier_reach, fr.n_max,
                               arrs)                        # [k, S, n+1]
        bl = _boundary_rows(fr, front, False, lambda ref, v: ref.max(v))
        D0 = _gather_boundary_matrix(fr, bl, fill=False)
        with tracing.span("repro.cache.closure", kind="reach"):
            C = bes.bool_closure(D0)
        cache = RvsetCache(fr=fr, arrays=arrs, bl_frontier=bl, closure=C,
                           part_b=fr.boundary_owner())
        fr.rvset_cache = cache
    if with_dist and cache.bl_dist is None:
        front = _each_fragment(engine.local_frontier_dist, fr.n_max,
                               cache.arrays)
        bl_d = _boundary_rows(fr, front, jnp.int32(INF),
                              lambda ref, v: ref.min(v))
        W0 = _gather_boundary_matrix(fr, bl_d, fill=INF)
        cache.bl_dist = bl_d
        with tracing.span("repro.cache.closure", kind="dist"):
            cache.dist_closure = bes.tropical_closure(W0)
    return cache


def _each_fragment(frontier_fn, n_max: int, arrs) -> jax.Array:
    """All-sources fixpoint of every fragment, one fragment at a time:
    [k, S, n_max+1].  A ``lax.map``, not a vmap, so the fixpoint's [S, E]
    message buffers exist for one fragment at a time — vmapped over all k
    fragments of a full-size graph they exceed a chip's memory."""
    return jax.lax.map(
        lambda a: frontier_fn(*a, n_max=n_max),
        (arrs["esrc"], arrs["edst"], arrs["src_local"]))


def _gather_boundary_matrix(fr: Fragmentation, bl, fill):
    """D0[u, w] = cached frontier of in-node u read at the stub slot of
    boundary node w inside u's fragment (pad slot column carries ``fill``)."""
    nb = fr.n_boundary
    if nb == 0:
        return jnp.zeros((0, 0), bl.dtype)
    cols = fr.arrays["tgt_local"][fr.boundary_owner()][:, :nb]   # [nb, nb]
    return jnp.take_along_axis(bl, jnp.asarray(cols), axis=1)


def get_rvset_cache(fr: Fragmentation, with_dist: bool = False) -> RvsetCache:
    cache = fr.rvset_cache
    if cache is None or (with_dist and cache.bl_dist is None):
        cache = prepare_rvset_cache(fr, with_dist=with_dist)
    return cache


# ---------------------------------------------------------------------------
# replicated combine stage (shared by both backends: the vmap batched
# kernels below and the sharded one-collective programs in core.distributed)
# ---------------------------------------------------------------------------

def combine_bool(direct, sb, tc, C):
    """Boolean combine of the per-query phase through a closure:
    ``ans = direct | OR_u (sb (or-and) C)[u] & tc[u]``.

    ``sb``/``tc`` [N, side], ``C`` [side, side] with ``side = nb`` for plain
    reachability or ``nb * |Q|`` for the product-automaton (RPQ) case —
    the algebra is identical, only the state expansion differs.
    """
    if C.shape[0] == 0:
        return direct
    from ..kernels.bool_matmul.ops import or_and_matmul
    sbc = or_and_matmul(sb, C)                             # [N, side]
    return direct | jnp.any(sbc & tc, axis=1)


def combine_dist(direct, sb, tc, Cd):
    """Tropical twin of :func:`combine_bool`:
    ``min(direct, min_u (sb (min-plus) Cd)[u] + tc[u])`` clipped at INF."""
    if Cd.shape[0] == 0:
        return jnp.minimum(direct, INF)
    from ..kernels.tropical_matmul.ops import min_plus_matmul
    sbc = min_plus_matmul(sb, Cd)                          # [N, nb]
    via = jnp.min(jnp.minimum(sbc + tc, INF), axis=1)
    return jnp.minimum(jnp.minimum(direct, via), INF)


# ---------------------------------------------------------------------------
# per-device local stage (sharded backend: each device contributes its own
# fragment's D0/W0 rows, per-pair s-rows and t-column entries, which ride
# the ONE collective of core.distributed.dis_*_batch_sharded)
# ---------------------------------------------------------------------------

def local_stage_reach(esrc, edst, src_local, s_slot, t_slot, srcidx, own,
                      tgt_mine, *, n_max: int):
    """One device's local stage of a fused reach batch.

    Runs this fragment's all-sources fixpoint and N per-pair single-source
    propagations, then extracts the fragment's contributions: its owned
    ``D0`` rows, the s-row and direct bit of every pair whose source it
    owns, and the t-column entries of its own in-nodes.  Shapes:
    ``s_slot``/``t_slot`` [N] (local slot of s_j / t_j here, ``n_max`` if
    absent); ``srcidx`` [nb] (boundary position -> source-row index here,
    pad row elsewhere); ``own`` [nb] ownership mask; ``tgt_mine`` [nb]
    (stub slot of boundary w here).  Returns ``(d0 [nb, nb], sb [N, nb],
    direct [N], tc [N, nb])`` — all-false outside this device's ownership,
    so the cross-device merge is a plain bitwise OR.
    """
    F = engine.local_frontier_reach(esrc, edst, src_local,
                                    n_max=n_max)           # [S, n+1]
    rows = jnp.take(F, srcidx, axis=0)                     # [nb, n+1]
    d0 = jnp.take(rows, tgt_mine, axis=1) & own[:, None]   # [nb, nb]
    fS = jax.vmap(lambda sl: engine.single_source_reach(
        esrc, edst, sl, n_max=n_max))(s_slot)              # [N, n+1]
    sb = jnp.take(fS, tgt_mine, axis=1)                    # [N, nb]
    direct = jnp.take_along_axis(fS, t_slot[:, None], axis=1)[:, 0]
    tc = jnp.take(rows, t_slot, axis=1).T & own[None, :]   # [N, nb]
    return d0, sb, direct, tc


def local_stage_dist(esrc, edst, src_local, s_slot, t_slot, srcidx, own,
                     tgt_mine, *, n_max: int):
    """Tropical twin of :func:`local_stage_reach`: the semiring zero is INF,
    so non-owned entries ship INF and the cross-device merge is a min.
    Returns ``(w0 [nb, nb], sb [N, nb], direct [N], tc [N, nb])`` int32."""
    F = engine.local_frontier_dist(esrc, edst, src_local,
                                   n_max=n_max)            # [S, n+1]
    rows = jnp.take(F, srcidx, axis=0)                     # [nb, n+1]
    w0 = jnp.where(own[:, None], jnp.take(rows, tgt_mine, axis=1), INF)
    fS = jax.vmap(lambda sl: engine.single_source_dist(
        esrc, edst, sl, n_max=n_max))(s_slot)              # [N, n+1]
    sb = jnp.take(fS, tgt_mine, axis=1)                    # [N, nb]
    direct = jnp.take_along_axis(fS, t_slot[:, None], axis=1)[:, 0]
    tc = jnp.where(own[None, :], jnp.take(rows, t_slot, axis=1).T, INF)
    return w0, sb, direct, tc


def local_stage_rpq(esrc, edst, src_local, src_row, tgt_local, labels, gids,
                    q_labels, q_trans, q_start, s_slot, t_slot, s_gids,
                    t_gids, local_b, mine, *, n_max: int, B: int):
    """Product-automaton local stage of a fused RPQ batch (one device).

    The query-independent part is this fragment's product rvset rows
    (``local_eval_regular`` with the s/t sentinels matched off, exactly
    like :func:`product_closure`); the per-pair part is one forward product
    propagation from ``(s_j, u_s)`` and one reverse product propagation to
    ``(t_j, u_t)`` per pair.  ``local_b`` [nb] is the local slot of each
    boundary node inside its *owner*; ``mine`` [nb] masks the in-nodes this
    device owns.  Returns ``(d0 [(nb*Q), (nb*Q)], sb [N, nb*Q], direct [N],
    tc [N, nb*Q])``.
    """
    Q = q_labels.shape[0]
    nb = B - 2
    rloc = engine.local_eval_regular(
        esrc, edst, src_local, src_row, tgt_local, labels, gids,
        q_labels, q_trans, jnp.int32(n_max), jnp.int32(n_max),
        jnp.int32(NO_NODE), jnp.int32(NO_NODE), n_max=n_max, B=B)
    # boundary rows/cols (b, q) sit at b*Q + q, so b < nb is a prefix: a
    # plain 2-D slice (a [B, Q, B, Q] view would pad Q to 128 TPU lanes)
    d0 = rloc[:nb * Q, :nb * Q]
    f = jax.vmap(lambda sl, sg, tg: engine.single_source_regular(
        esrc, edst, labels, gids, q_labels, q_trans, sl, q_start, sg, tg,
        n_max=n_max))(s_slot, s_gids, t_gids)              # [N, n+1, Q]
    direct = jnp.take_along_axis(f[:, :, Q - 1], t_slot[:, None],
                                 axis=1)[:, 0]             # [N]
    sb = jnp.take(f, tgt_local[:nb], axis=1)               # [N, nb, Q]
    rev = jax.vmap(lambda ts, sg, tg: engine.reverse_target_regular(
        esrc, edst, labels, gids, q_labels, q_trans, ts, sg, tg,
        n_max=n_max))(t_slot, s_gids, t_gids)              # [N, n+1, Q]
    tc = jnp.take(rev, local_b, axis=1) & mine[None, :, None]  # [N, nb, Q]
    N = f.shape[0]
    return d0, sb.reshape(N, nb * Q), direct, tc.reshape(N, nb * Q)


# -- packed variants: one device owning SEVERAL fragments (k >> d) ----------
#
# Each wrapper runs its per-fragment stage over the leading owned-fragments
# axis (fpd) one fragment at a time and folds the contributions on-device —
# OR for the Boolean kinds, min for the tropical one.  The merge is exact
# for the same reason the cross-device collective is: every d0/sb row and
# tc column is computed by exactly one fragment (the others contribute the
# semiring zero), and ownership stays disjoint whether fragments sit on
# different devices or share one.  Inert pad fragments (pad-only edge
# lists, all-false ownership masks, absent s/t slots) contribute
# zeros/INF and their propagations converge in zero while_loop iterations,
# so short devices cost nothing.  A scan rather than a vmap: the stages'
# propagation buffers then exist for one fragment at a time, which is what
# lets a full-size fragment stack fit a chip's memory.

def _fold_owned(stage, owned, zero, axis_name: str):
    """``stage(*owned_i)`` for every owned fragment ``i`` (each array in
    ``owned`` has a leading [fpd] axis), folded elementwise with the
    semiring sum whose identity is ``zero`` (False: OR; INF: min).  Runs
    inside shard_map over ``axis_name``, where the fold's carry must be
    device-varying like the data it accumulates."""
    merge = jnp.logical_or if zero is False else jnp.minimum
    out = jax.eval_shape(stage, *(x[0] for x in owned))
    init = jax.lax.pcast(tuple(jnp.full(o.shape, zero, o.dtype)
                               for o in out), axis_name, to="varying")

    def step(acc, frag):
        return tuple(merge(a, o) for a, o in zip(acc, stage(*frag))), None

    return jax.lax.scan(step, init, tuple(owned))[0]


def local_stage_reach_packed(esrc, edst, src_local, s_slot, t_slot, srcidx,
                             own, tgt_mine, *, n_max: int, axis_name: str):
    """:func:`local_stage_reach` for a device owning ``fpd`` fragments —
    every argument gains a leading ``[fpd, ...]`` axis; the returned
    ``(d0, sb, direct, tc)`` are OR-merged over it (shapes as unpacked).
    ``axis_name``: the shard_map mesh axis this runs under."""
    return _fold_owned(
        functools.partial(local_stage_reach, n_max=n_max),
        (esrc, edst, src_local, s_slot, t_slot, srcidx, own, tgt_mine),
        False, axis_name)


def local_stage_dist_packed(esrc, edst, src_local, s_slot, t_slot, srcidx,
                            own, tgt_mine, *, n_max: int, axis_name: str):
    """Tropical twin of :func:`local_stage_reach_packed`: min-merge over
    the owned-fragments axis (non-owners ship INF, the tropical zero)."""
    return _fold_owned(
        functools.partial(local_stage_dist, n_max=n_max),
        (esrc, edst, src_local, s_slot, t_slot, srcidx, own, tgt_mine),
        int(INF), axis_name)


def local_stage_rpq_packed(esrc, edst, src_local, src_row, tgt_local, labels,
                           gids, q_labels, q_trans, q_start, s_slot, t_slot,
                           s_gids, t_gids, local_b, mine, *, n_max: int,
                           B: int, axis_name: str):
    """:func:`local_stage_rpq` over the owned-fragments axis.  Per-fragment
    arguments carry ``[fpd, ...]``; the automaton (``q_*``), the pair gids
    and ``local_b`` stay replicated."""
    def stage(es, ed, sl, sr, tl, lab, gid, ss, ts, mn):
        return local_stage_rpq(es, ed, sl, sr, tl, lab, gid, q_labels,
                               q_trans, q_start, ss, ts, s_gids, t_gids,
                               local_b, mn, n_max=n_max, B=B)

    return _fold_owned(stage, (esrc, edst, src_local, src_row, tgt_local,
                               labels, gids, s_slot, t_slot, mine), False,
                       axis_name)


# ---------------------------------------------------------------------------
# batched per-query phase (one jitted call for N pairs)
# ---------------------------------------------------------------------------

def fragment_blocks(n_rows: int, k: int) -> int:
    """Fragment blocks the local stage of an ``n_rows`` batch relaxes over
    ``k`` fragments: every fragment once the batch has as many rows, else
    one per row (its distinct source fragments, padded by repeats)."""
    return min(n_rows, k)


def _union_edges(esrc, edst, blocks, n_max: int):
    """The edge lists of the fragments ``blocks`` [m] (``None``: all k, in
    order) as one list over ``m*(n_max+1)`` slots, block p's shifted by
    ``p*(n_max+1)`` and the whole sorted by destination: ``(es, ed)``
    [m*E].  Pad edges keep their self-loop, on their block's pad slot."""
    if blocks is not None:
        esrc = jnp.take(esrc, blocks, axis=0)
        edst = jnp.take(edst, blocks, axis=0)
    off = jnp.arange(esrc.shape[0], dtype=esrc.dtype)[:, None] * (n_max + 1)
    ed, es = jax.lax.sort(((edst + off).reshape(-1),
                           (esrc + off).reshape(-1)),
                          num_keys=1, is_stable=False)
    return es, ed


def _relax_lanes(es, ed, F, tropical: bool):
    """Fixpoint of the lanes ``F [m*(n_max+1), N]`` (one column per row)
    over the sorted union edge list: each round one row gather by ``es``
    and one sorted row reduction by ``ed``.  The rounds, the +1 and the
    INF cap are ``engine._propagate_dist``'s (``_propagate_bool``'s when
    not ``tropical``), so each column reaches the same fixpoint in the
    same rounds as its single-source propagation."""
    n_slots = F.shape[0]

    def step(state):
        cur, _ = state
        msgs = jnp.take(cur, es, axis=0)                       # [m*E, N]
        if tropical:
            agg = jax.ops.segment_min(msgs + 1, ed, num_segments=n_slots,
                                      indices_are_sorted=True)
            new = jnp.minimum(cur, agg)
            new = jnp.where(new > INF, INF, new)
        else:
            agg = jax.ops.segment_max(msgs.astype(jnp.int8), ed,
                                      num_segments=n_slots,
                                      indices_are_sorted=True)
            new = cur | (agg > 0)
        return new, jnp.any(new != cur)

    init = jnp.any(F < INF) if tropical else jnp.any(F)
    return jax.lax.while_loop(lambda st: st[1], step, (F, init))[0]


def _lane_frontiers(esrc, edst, blocks, pos, s_slot, *, n_max: int,
                    tropical: bool):
    """Single-source frontiers of N rows as lanes over the union of m
    fragment blocks: ``F [m*(n_max+1), N]``, row i in column i and in the
    slots of its block, ``F[pos[i]*(n_max+1) + v, i]`` being what
    ``engine.single_source_*`` gives for slot v of its fragment.

    ``blocks`` [m] names the fragments (``None``: all k, and then ``pos``
    is the source fragment itself; see :func:`fragment_blocks`), ``pos``
    [N] row i's block, ``s_slot`` [N] its source slot (``n_max``: none).
    Blocks share no edge, so the other blocks of a column stay at the
    semiring zero (DESIGN.md Sec. 3.1)."""
    N = s_slot.shape[0]
    zero, one, dtype = ((INF, 0, jnp.int32) if tropical
                        else (False, True, jnp.bool_))
    with jax.named_scope("gather"):
        with jax.named_scope("union_edges"):
            es, ed = _union_edges(esrc, edst, blocks, n_max)
        m = esrc.shape[0] if blocks is None else blocks.shape[0]
        F = jnp.full((m * (n_max + 1), N), zero, dtype)
        F = F.at[pos * (n_max + 1) + s_slot, jnp.arange(N)].set(
            jnp.where(s_slot < n_max, one, zero))
    with jax.named_scope("local_stage"):
        return _relax_lanes(es, ed, F, tropical)


def _batch_local(esrc, edst, tgt_local, bl, blocks, pos, s_slot,
                 t_slot_sfrag, t_cols, *, n_max: int, tropical: bool):
    """Local stage and gathers of a batch, shared by both semirings:
    ``(direct [N], sb [N, nb], tc [N, nb])``."""
    N, nb = s_slot.shape[0], bl.shape[0]
    F = _lane_frontiers(esrc, edst, blocks, pos, s_slot, n_max=n_max,
                        tropical=tropical)
    with jax.named_scope("gather"):
        base, lane = pos * (n_max + 1), jnp.arange(N)
        frag_s = pos if blocks is None else jnp.take(blocks, pos)
        direct = F[base + t_slot_sfrag, lane]                  # [N]
        tgt_s = jnp.take(tgt_local, frag_s, axis=0)[:, :nb]    # [N, nb]
        sb = F[base[:, None] + tgt_s, lane[:, None]]           # [N, nb]
        tc = jax.vmap(lambda c: bl[jnp.arange(nb), c])(t_cols)  # [N, nb]
    return direct, sb, tc


@functools.partial(jax.jit, static_argnames=("n_max",))
def _batch_reach_kernel(esrc, edst, tgt_local, bl, C, blocks, pos, s_slot,
                        t_slot_sfrag, t_cols, *, n_max: int):
    """N pairs -> N answers.  Shapes: esrc/edst [k, E]; tgt_local [k, B];
    bl [nb, n+1]; C [nb, nb]; blocks [m] or None; pos/s_slot/t_slot_sfrag
    [N]; t_cols [N, nb] (slot of t_j inside the fragment owning boundary
    u).  ``_batch_inputs`` makes the per-batch ones.

    Its stages carry the named scopes ``local_stage``, ``gather`` and
    ``combine``, which a profiler trace reads its device time by."""
    direct, sb, tc = _batch_local(esrc, edst, tgt_local, bl, blocks, pos,
                                  s_slot, t_slot_sfrag, t_cols, n_max=n_max,
                                  tropical=False)
    with jax.named_scope("combine"):
        return combine_bool(direct, sb, tc, C)


@functools.partial(jax.jit, static_argnames=("n_max",))
def _batch_dist_kernel(esrc, edst, tgt_local, bl_d, Cd, blocks, pos, s_slot,
                       t_slot_sfrag, t_cols, *, n_max: int):
    """Tropical twin of :func:`_batch_reach_kernel`: N distances (INF if
    unreachable), under the same named scopes."""
    direct, sb, tc = _batch_local(esrc, edst, tgt_local, bl_d, blocks, pos,
                                  s_slot, t_slot_sfrag, t_cols, n_max=n_max,
                                  tropical=True)
    with jax.named_scope("combine"):
        return combine_dist(direct, sb, tc, Cd)


def _batch_inputs(fr: Fragmentation, cache: RvsetCache,
                  pairs: np.ndarray):
    """Host-side per-batch index arrays (pure numpy gathers), in the
    kernels' order from ``blocks`` on.  A batch of fewer rows than
    fragments relaxes only its distinct source fragments, padded by
    repeats to one block per row; a larger one relaxes all of them and
    needs no block list (:func:`fragment_blocks`)."""
    ss, tt = pairs[:, 0], pairs[:, 1]
    slot_of = fr.slot_index()                              # [n, k]
    frag_s = fr.part[ss].astype(np.int32)
    s_slot = fr.owner_local[ss].astype(np.int32)
    t_slot_sfrag = slot_of[tt, frag_s]                     # [N]
    # slot of t_j inside the fragment owning each boundary node u
    t_cols = slot_of[tt][:, cache.part_b]                  # [N, nb]
    if fragment_blocks(len(pairs), fr.k) == fr.k:
        blocks, pos = None, frag_s
    else:
        uniq, pos = np.unique(frag_s, return_inverse=True)
        blocks = jnp.asarray(np.resize(uniq, len(pairs)).astype(np.int32))
    return (blocks, jnp.asarray(pos.astype(np.int32)), jnp.asarray(s_slot),
            jnp.asarray(t_slot_sfrag), jnp.asarray(t_cols))


def _as_pairs(pairs) -> np.ndarray:
    p = np.asarray(pairs, dtype=np.int64)
    if p.ndim != 2 or p.shape[1] != 2:
        raise ValueError(f"pairs must be [N, 2], got {p.shape}")
    return p


def dis_reach_batch(fr: Fragmentation, pairs) -> np.ndarray:
    """Answer N (s, t) reachability queries in one jitted call against the
    amortized rvset cache.  Returns [N] bool."""
    pairs = _as_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=bool)
    cache = get_rvset_cache(fr)
    arrs = cache.arrays
    with tracing.span("repro.session.inputs"):
        inputs = _batch_inputs(fr, cache, pairs)
    with tracing.span("repro.session.device"):
        return np.asarray(_batch_reach_kernel(
            arrs["esrc"], arrs["edst"], arrs["tgt_local"],
            cache.bl_frontier, cache.closure, *inputs, n_max=fr.n_max))


def dis_dist_batch(fr: Fragmentation, pairs,
                   bound: Optional[int] = None) -> np.ndarray:
    """N shortest distances (or bounded-reachability answers when ``bound``
    is given: dist <= bound).  Returns [N] int64 distances with -1 for
    unreachable, or [N] bool when ``bound`` is not None."""
    pairs = _as_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=bool if bound is not None else np.int64)
    cache = get_rvset_cache(fr, with_dist=True)
    arrs = cache.arrays
    with tracing.span("repro.session.inputs"):
        inputs = _batch_inputs(fr, cache, pairs)
    with tracing.span("repro.session.device"):
        d = np.asarray(_batch_dist_kernel(
            arrs["esrc"], arrs["edst"], arrs["tgt_local"],
            cache.bl_dist, cache.dist_closure, *inputs,
            n_max=fr.n_max)).astype(np.int64)
    if bound is not None:
        return d <= bound
    d[d >= int(INF)] = -1
    return d


# ---------------------------------------------------------------------------
# cached single-query wrappers (batch of one)
# ---------------------------------------------------------------------------

def reach_cached(fr: Fragmentation, s: int, t: int) -> bool:
    return bool(dis_reach_batch(fr, [(s, t)])[0])


def dist_cached(fr: Fragmentation, s: int, t: int) -> Optional[int]:
    d = int(dis_dist_batch(fr, [(s, t)])[0])
    return None if d < 0 else d


# ---------------------------------------------------------------------------
# regular (RPQ) cached path
# ---------------------------------------------------------------------------

def _qa_key(qa: QueryAutomaton) -> Tuple:
    return qa.cache_key()


def product_closure(fr: Fragmentation, qa: QueryAutomaton) -> jax.Array:
    """Query-independent product-automaton closure [(nb*Q), (nb*Q)].

    Sound because the Glushkov automaton's u_s has no incoming and u_t no
    outgoing transitions: neither s-only nor t-only states can occur strictly
    inside a boundary-to-boundary path, so matching them off (NO_NODE gid)
    loses nothing the per-query phase doesn't re-add.
    """
    cache = get_rvset_cache(fr)
    key = _qa_key(qa)
    C = cache.rpq_closures.get(key)
    if C is not None:
        # true LRU: a hit moves the key back to the MRU end of the (insert-
        # ordered) dict, so a hot automaton is never FIFO-evicted by churn
        cache.rpq_closures.pop(key)
        cache.rpq_closures[key] = C
        return C
    arrs = cache.arrays
    q_labels = jnp.asarray(qa.state_labels)
    q_trans = jnp.asarray(qa.trans)
    n_max, B, Q = fr.n_max, fr.B, qa.n_states

    def fold(D, frag):
        # one fragment's product rvset at a time, OR-ed into the matrix:
        # the [S, Q, E, Q] propagation buffers of all k fragments at once
        # would not fit a chip at full size
        rloc = engine.local_eval_regular(
            *frag, q_labels, q_trans, jnp.int32(n_max), jnp.int32(n_max),
            jnp.int32(NO_NODE), jnp.int32(NO_NODE), n_max=n_max, B=B)
        return D | rloc, None

    with tracing.span("repro.cache.closure", kind="rpq"):
        D, _ = jax.lax.scan(fold, jnp.zeros((B * Q, B * Q), bool),
                            tuple(arrs[name] for name in (
                                "esrc", "edst", "src_local", "src_row",
                                "tgt_local", "labels", "gids")))
        nb = fr.n_boundary
        D = D[:nb * Q, :nb * Q]          # the boundary rows/cols: a prefix
        C = bes.bool_closure(D)
    # bound the per-automaton cache: each closure is (nb*Q)^2 bools, and a
    # server facing user-supplied regexes must not grow without limit.
    # dict order is recency order (hits re-insert at the MRU end), so the
    # first key is the least recently used one
    while len(cache.rpq_closures) >= MAX_RPQ_CLOSURES:
        cache.rpq_closures.pop(next(iter(cache.rpq_closures)))
    cache.rpq_closures[key] = C
    return C


@functools.partial(jax.jit, static_argnames=("n_max",))
def _batch_rpq_kernel(esrc, edst, labels, gids, tgt_local, q_labels, q_trans,
                      q_start, C, part_b, local_b, frag_s, s_slot,
                      t_slot_sfrag, t_slots, s_gids, t_gids, *, n_max: int):
    """N pairs -> N answers for ONE automaton against its cached product
    closure.  Shapes: esrc/edst/labels/gids [k, ...]; tgt_local [k, B];
    C [(nb*Q), (nb*Q)]; part_b/local_b [nb]; frag_s/s_slot/t_slot_sfrag/
    s_gids/t_gids [N]; t_slots [N, k] (slot of t_j in every fragment).

    Per pair: one forward product propagation from (s, u_s) on s's fragment
    and k reverse product propagations to (t, u_t) (one per fragment — the
    t-column), both vmapped over the batch; then ONE or-and matmul
    [N, nb*Q] x [(nb*Q), (nb*Q)] composes them through the closure.
    """
    Q = q_labels.shape[0]
    nb = part_b.shape[0]
    with jax.named_scope("gather"):
        es = jnp.take(esrc, frag_s, axis=0)                # [N, E]
        ed = jnp.take(edst, frag_s, axis=0)
        lab = jnp.take(labels, frag_s, axis=0)
        gid = jnp.take(gids, frag_s, axis=0)
    with jax.named_scope("local_stage"):
        f = jax.vmap(lambda a, b, c, d, sl, sg, tg:
                     engine.single_source_regular(
                         a, b, c, d, q_labels, q_trans, sl, q_start, sg, tg,
                         n_max=n_max))(es, ed, lab, gid, s_slot, s_gids,
                                       t_gids)             # [N, n+1, Q]
        rev = jax.vmap(lambda ts, sg, tg: jax.vmap(
            lambda a, b, c, d, tslot: engine.reverse_target_regular(
                a, b, c, d, q_labels, q_trans, tslot, sg, tg,
                n_max=n_max))(esrc, edst, labels, gids, ts))(
            t_slots, s_gids, t_gids)                       # [N, k, n+1, Q]
    with jax.named_scope("gather"):
        direct = jnp.take_along_axis(f[:, :, Q - 1], t_slot_sfrag[:, None],
                                     axis=1)[:, 0]         # [N]
        if nb == 0:
            return direct
        tgt_s = jnp.take(tgt_local, frag_s, axis=0)[:, :nb]  # [N, nb]
        sb = jnp.take_along_axis(f, tgt_s[:, :, None], axis=1)  # [N, nb, Q]
        # spare boundary slots read the (all-false) pad row of rev via
        # local_b
        tc = rev[:, part_b, local_b, :]                    # [N, nb, Q]
    N = f.shape[0]
    with jax.named_scope("combine"):
        return combine_bool(direct, sb.reshape(N, nb * Q),
                            tc.reshape(N, nb * Q), C)


def dis_rpq_batch(fr: Fragmentation, pairs, qa: QueryAutomaton) -> np.ndarray:
    """Answer N (s, t) regular path queries for one automaton in one jitted
    call against the cached product closure.  Returns [N] bool.

    One compiled program per (automaton, batch-shape) pair — the session
    planner pads batch sizes to buckets, so a mixed workload with R
    distinct automata steady-states at R compiled executions per batch.
    """
    pairs = _as_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=bool)
    C = product_closure(fr, qa)
    cache = get_rvset_cache(fr)
    arrs = cache.arrays
    ss, tt = pairs[:, 0], pairs[:, 1]
    with tracing.span("repro.session.inputs"):
        slot_of = fr.slot_index()
        frag_s = fr.part[ss].astype(np.int32)
        inputs = (
            jnp.asarray(qa.state_labels), jnp.asarray(qa.trans),
            jnp.int32(qa.start), C, jnp.asarray(cache.part_b),
            jnp.asarray(fr.boundary_local()), jnp.asarray(frag_s),
            jnp.asarray(fr.owner_local[ss].astype(np.int32)),
            jnp.asarray(slot_of[tt, frag_s]), jnp.asarray(slot_of[tt, :]),
            jnp.asarray(ss.astype(np.int32)),
            jnp.asarray(tt.astype(np.int32)))
    with tracing.span("repro.session.device"):
        ans = np.array(_batch_rpq_kernel(   # copy: jax buffers are read-only
            arrs["esrc"], arrs["edst"], arrs["labels"], arrs["gids"],
            arrs["tgt_local"], *inputs, n_max=fr.n_max))
    ans[ss == tt] = bool(qa.nullable)      # convention: s==t is |R|-free
    return ans


def rpq_cached(fr: Fragmentation, s: int, t: int, qa: QueryAutomaton) -> bool:
    """Cached disRPQ (batch of one): per-automaton product closure
    (amortized) + one forward and k reverse product propagations."""
    if s == t:
        return bool(qa.nullable)
    return bool(dis_rpq_batch(fr, [(s, t)], qa)[0])
