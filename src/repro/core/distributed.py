"""shard_map engine: fragments packed onto a device mesh (d <= k).

This is the production path: a :class:`~repro.core.fragments.Placement`
maps every fragment to a mesh device (several fragments per device when
``k > d``); each device runs localEval on its owned fragments with *zero*
communication — a vmap over the owned-fragments axis, merged on-device —
then a single collective assembles the dependency matrix, and evalDG runs
replicated (see DESIGN.md Sec. 2 for why replication beats a coordinator on
a torus).

Performance-guarantee mapping (checked by tests/test_guarantees.py):
  * "each site visited once"        -> exactly one collective in the HLO;
  * "traffic O(|V_f|^2)" bits       -> the collective payload is the B x B
    Boolean matrix bitpacked into uint32 words (kernels.bitpack_ops): 8x
    fewer bits than the seed's uint8 shipping, independent of |G|.  pmax
    over packed words is exact because every payload row is owned by
    exactly one fragment (all other devices contribute zero words);
  * "time O(|F_m| |V_f|)"           -> per-device localEval work, done in
    parallel; evalDG adds O(diam(G_f) |V_f|^2) replicated FLOPs.

``dis_reach_batch_sharded`` is the batched equivalent (DESIGN.md Sec. 3.3):
one shard_map program answers N pairs with a SINGLE packed collective that
carries the boundary matrix rows and all per-pair s-row / t-column
contributions together.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import tracing
from . import cache as _cache
from . import engine
from ..kernels.bitpack_ops.ops import pack_payload, unpack_payload
from .automaton import QueryAutomaton
from .bes import bool_closure, tropical_closure
from .fragments import Fragmentation, Placement, query_slots

FRAG_AXIS = "frag"


def fragment_mesh(k: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh with one shard per fragment."""
    devices = np.array(jax.devices() if devices is None else devices)
    k = len(devices) if k is None else k
    assert len(devices) >= k, f"need >= {k} devices, have {len(devices)}"
    return jax.make_mesh((k,), (FRAG_AXIS,), devices=devices[:k])


def _shard_args(fr: Fragmentation, s: int, t: int):
    qs = query_slots(fr, s, t)
    args = {k: jnp.array(v) for k, v in fr.arrays.items()}
    args["s_local"] = jnp.asarray(qs["s_local"])
    args["t_local"] = jnp.asarray(qs["t_local"])
    return args


def _specs():
    sharded = P(FRAG_AXIS)
    return dict(esrc=sharded, edst=sharded, src_local=sharded,
                src_row=sharded, tgt_local=sharded, labels=sharded,
                gids=sharded, n_local=sharded,
                s_local=sharded, t_local=sharded)


def dis_reach_sharded(fr: Fragmentation, s: int, t: int,
                      mesh: Optional[Mesh] = None):
    """disReach over a device mesh; returns (answer, D) replicated —
    D is None for the trivial s == t case (nothing is evaluated)."""
    if s == t:
        return True, None
    mesh = mesh or fragment_mesh(fr.k)
    assert mesh.devices.size == fr.k, "one device (shard) per fragment"
    args = _shard_args(fr, s, t)
    specs = _specs()
    in_specs = tuple(specs[k] for k in
                     ("esrc", "edst", "src_local", "src_row", "tgt_local",
                      "s_local", "t_local"))
    tgt_cols, src_rows, bt = _answer_masks(fr, t)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(), P()))
    def sharded_reach_one(esrc, edst, src_local, src_row, tgt_local,
                          s_local, t_local):
        rloc = engine.local_eval_reach(
            esrc[0], edst[0], src_local[0], src_row[0], tgt_local[0],
            s_local[0], t_local[0], n_max=fr.n_max, B=fr.B)
        # the single collective: OR-reduce the bitpacked boundary matrices
        # (row ownership is disjoint, so pmax over uint32 words == OR)
        Dp = jax.lax.pmax(pack_payload(rloc), FRAG_AXIS)
        D = unpack_payload(Dp, fr.B)
        ans = engine.evaldg_reach(D, src_rows, tgt_cols)
        return ans, D

    ans, D = jax.jit(sharded_reach_one)(*(args[k] for k in
                            ("esrc", "edst", "src_local", "src_row",
                             "tgt_local", "s_local", "t_local")))
    return bool(ans), np.asarray(D)


def _answer_masks(fr: Fragmentation, t: int):
    tgt_cols = np.zeros(fr.B, dtype=bool)
    tgt_cols[fr.T_COL] = True
    bt = int(fr.b_index[t])
    if bt >= 0:
        tgt_cols[bt] = True
    src_rows = np.zeros(fr.B, dtype=bool)
    src_rows[fr.S_ROW] = True
    return jnp.asarray(tgt_cols), jnp.asarray(src_rows), bt


def dis_rpq_sharded(fr: Fragmentation, s: int, t: int, qa: QueryAutomaton,
                    mesh: Optional[Mesh] = None):
    if s == t:
        return bool(qa.nullable)
    mesh = mesh or fragment_mesh(fr.k)
    args = _shard_args(fr, s, t)
    Q = qa.n_states
    q_labels = jnp.asarray(qa.state_labels)
    q_trans = jnp.asarray(qa.trans)

    src_rows = np.zeros(fr.B * Q, dtype=bool)
    src_rows[fr.S_ROW * Q + qa.start] = True
    tgt_cols = np.zeros(fr.B * Q, dtype=bool)
    tgt_cols[fr.T_COL * Q + qa.final] = True
    bt = int(fr.b_index[t])
    if bt >= 0:
        tgt_cols[bt * Q + qa.final] = True
    src_rows, tgt_cols = jnp.asarray(src_rows), jnp.asarray(tgt_cols)

    names = ("esrc", "edst", "src_local", "src_row", "tgt_local", "labels",
             "gids", "s_local", "t_local")
    specs = _specs()
    in_specs = tuple(specs[k] for k in names)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    def sharded_rpq_one(esrc, edst, src_local, src_row, tgt_local, labels,
                        gids, s_local, t_local):
        rloc = engine.local_eval_regular(
            esrc[0], edst[0], src_local[0], src_row[0], tgt_local[0],
            labels[0], gids[0], q_labels, q_trans,
            s_local[0], t_local[0], jnp.int32(s), jnp.int32(t),
            n_max=fr.n_max, B=fr.B)
        Dp = jax.lax.pmax(pack_payload(rloc), FRAG_AXIS)
        D = unpack_payload(Dp, fr.B * Q)
        return engine.evaldg_reach(D, src_rows, tgt_cols)

    ans = jax.jit(sharded_rpq_one)(*(args[k] for k in names))
    return bool(ans)


def lower_reach_hlo(fr: Fragmentation, s: int, t: int,
                    mesh: Optional[Mesh] = None) -> str:
    """Lowered HLO text of the sharded disReach — used by tests to assert
    the one-collective-round guarantee structurally."""
    mesh = mesh or fragment_mesh(fr.k)
    args = _shard_args(fr, s, t)
    specs = _specs()
    names = ("esrc", "edst", "src_local", "src_row", "tgt_local",
             "s_local", "t_local")
    in_specs = tuple(specs[k] for k in names)
    tgt_cols, src_rows, _ = _answer_masks(fr, t)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    def sharded_reach_one(esrc, edst, src_local, src_row, tgt_local,
                          s_local, t_local):
        rloc = engine.local_eval_reach(
            esrc[0], edst[0], src_local[0], src_row[0], tgt_local[0],
            s_local[0], t_local[0], n_max=fr.n_max, B=fr.B)
        Dp = jax.lax.pmax(pack_payload(rloc), FRAG_AXIS)
        D = unpack_payload(Dp, fr.B)
        return engine.evaldg_reach(D, src_rows, tgt_cols)

    lowered = jax.jit(sharded_reach_one).lower(*(args[k] for k in names))
    return lowered.as_text()


# ---------------------------------------------------------------------------
# batched sharded engine: N pairs, ONE packed collective per fused group,
# for ALL THREE query classes (DESIGN.md Sec. 3.3)
# ---------------------------------------------------------------------------
#
# Shared structure (the local stage lives in core.cache.local_stage_*, the
# combine in core.cache.combine_*, so both backends evolve together): each
# device runs its owned fragments' query-independent rows (D0 / W0 /
# product rvset) plus the per-pair s-rows, direct entries, and t-column
# entries they own — vmapped over the owned-fragments axis and OR/min-
# merged on-device (core.cache.local_stage_*_packed) — concatenates
# everything into ONE payload of shape [side + 2N, side + 1] (side = nb,
# or nb*|Q| for RPQs; the extra column carries the per-pair direct
# answer), and a single collective merges it: psum over bitpacked uint32
# words for the Boolean payloads (no carries — every bit is computed on
# exactly one device: d0/sb rows by their owner, tc[:, u] by frag(u)),
# pmin over raw int32 for the tropical wire (exact because non-owners
# ship INF).  Closure + combine run replicated, exactly like evalDG.  The
# compiled programs are cached per (mesh, geometry, fpd, N) — fpd is the
# only shape the placement adds; the assignment itself rides in as packed
# argument data — so steady-state batches neither retrace nor recompile,
# and survive in-place deltas (no fragment data is baked in).

def _split_merged(merged, side: int, N: int):
    """Undo the payload concatenation: (d0, sb, direct, tc)."""
    return (merged[:side, :side], merged[side:side + N, :side],
            merged[side:side + N, side], merged[side + N:, :side])


def _resolve_placement(fr: Fragmentation, mesh: Optional[Mesh],
                       placement: Optional[Placement]):
    """Normalize (mesh, placement) for the packed sharded engines.

    Default placement is :meth:`Placement.balanced` over the mesh size (or
    over ``min(devices, k)`` when no mesh is given); default mesh is the
    first ``placement.d`` process devices.  Raises ValueError on any
    mismatch — including the d > k case, which the sharded engines cannot
    serve (a fragment is never split across devices)."""
    if placement is None:
        d = int(mesh.devices.size) if mesh is not None \
            else min(len(jax.devices()), fr.k)
        placement = Placement.balanced(fr, d)
    if placement.k != fr.k:
        raise ValueError(f"placement maps {placement.k} fragments but the "
                         f"fragmentation has {fr.k}")
    mesh = mesh or fragment_mesh(placement.d)
    if mesh.devices.size != placement.d:
        raise ValueError(f"mesh has {mesh.devices.size} devices but the "
                         f"placement expects {placement.d}")
    return mesh, placement


def _pack_rows(arr: np.ndarray, perm: np.ndarray, pad) -> np.ndarray:
    """Reorder a stacked [k, ...] per-fragment array into the device-major
    [d*fpd, ...] packed layout; pad slots (perm == -1) are filled with the
    array's inert value."""
    out = np.full((len(perm),) + arr.shape[1:], pad, dtype=arr.dtype)
    valid = perm >= 0
    out[valid] = arr[perm[valid]]
    return out


@functools.lru_cache(maxsize=64)
def _batch_reach_jitted(mesh: Mesh, nb: int, n_max: int, fpd: int, N: int):
    in_specs = tuple(P(FRAG_AXIS) for _ in range(8))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    def sharded_reach(esrc, edst, src_local, tgt_local, s_slot, t_slot,
                      srcidx, own):
        # each arg arrives [fpd, ...]: this device's owned fragments
        with jax.named_scope("local_stage"):
            d0, sb, direct, tc = _cache.local_stage_reach_packed(
                esrc, edst, src_local, s_slot, t_slot,
                srcidx, own, tgt_local[:, :nb], n_max=n_max,
                axis_name=FRAG_AXIS)
        with jax.named_scope("collective"):
            payload = jnp.concatenate([
                jnp.concatenate([d0, jnp.zeros((nb, 1), bool)], axis=1),
                jnp.concatenate([sb, direct[:, None]], axis=1),
                jnp.concatenate([tc, jnp.zeros((N, 1), bool)], axis=1),
            ], axis=0)                                     # [nb+2N, nb+1]
            merged = unpack_payload(
                jax.lax.psum(pack_payload(payload), FRAG_AXIS), nb + 1)
            d0_m, sb_m, direct_m, tc_m = _split_merged(merged, nb, N)
        # replicated: closure by repeated squaring + per-pair combine
        with jax.named_scope("closure"):
            C = bool_closure(d0_m)
        with jax.named_scope("combine"):
            return _cache.combine_bool(direct_m, sb_m, tc_m, C)

    return jax.jit(sharded_reach)


@functools.lru_cache(maxsize=64)
def _batch_dist_jitted(mesh: Mesh, nb: int, n_max: int, fpd: int, N: int):
    in_specs = tuple(P(FRAG_AXIS) for _ in range(8))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    def sharded_dist(esrc, edst, src_local, tgt_local, s_slot, t_slot,
                     srcidx, own):
        with jax.named_scope("local_stage"):
            w0, sb, direct, tc = _cache.local_stage_dist_packed(
                esrc, edst, src_local, s_slot, t_slot,
                srcidx, own, tgt_local[:, :nb], n_max=n_max,
                axis_name=FRAG_AXIS)
        with jax.named_scope("collective"):
            inf_b = jnp.full((nb, 1), engine.INF, jnp.int32)
            inf_n = jnp.full((N, 1), engine.INF, jnp.int32)
            payload = jnp.concatenate([
                jnp.concatenate([w0, inf_b], axis=1),
                jnp.concatenate([sb, direct[:, None]], axis=1),
                jnp.concatenate([tc, inf_n], axis=1),
            ], axis=0)                                     # [nb+2N, nb+1]
            # the ONE collective: min-reduce the int32 tropical wire —
            # exact because every entry is computed on exactly one device
            # (w0 and sb rows by their owner, tc[:, u] by frag(u)) and all
            # others ship INF, the tropical zero.  int32 rows do not
            # bitpack, so the wire carries the rows actually contributed,
            # never the B^2 matrix.
            merged = jax.lax.pmin(payload, FRAG_AXIS)
            w0_m, sb_m, direct_m, tc_m = _split_merged(merged, nb, N)
        with jax.named_scope("closure"):
            Cd = tropical_closure(w0_m)
        with jax.named_scope("combine"):
            return _cache.combine_dist(direct_m, sb_m, tc_m, Cd)

    return jax.jit(sharded_dist)


@functools.lru_cache(maxsize=64)
def _batch_rpq_jitted(mesh: Mesh, nb: int, n_max: int, B: int, Q: int,
                      q_start: int, fpd: int, N: int):
    side = nb * Q
    in_specs = tuple(P(FRAG_AXIS) for _ in range(10)) + \
        tuple(P() for _ in range(5))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=P())
    def sharded_rpq(esrc, edst, src_local, src_row, tgt_local, labels, gids,
                    s_slot, t_slot, mine, q_labels, q_trans, s_gids, t_gids,
                    local_b):
        with jax.named_scope("local_stage"):
            d0, sb, direct, tc = _cache.local_stage_rpq_packed(
                esrc, edst, src_local, src_row, tgt_local,
                labels, gids, q_labels, q_trans, jnp.int32(q_start),
                s_slot, t_slot, s_gids, t_gids, local_b, mine,
                n_max=n_max, B=B, axis_name=FRAG_AXIS)
        with jax.named_scope("collective"):
            payload = jnp.concatenate([
                jnp.concatenate([d0, jnp.zeros((side, 1), bool)], axis=1),
                jnp.concatenate([sb, direct[:, None]], axis=1),
                jnp.concatenate([tc, jnp.zeros((N, 1), bool)], axis=1),
            ], axis=0)                             # [side+2N, side+1]
            merged = unpack_payload(
                jax.lax.psum(pack_payload(payload), FRAG_AXIS), side + 1)
            d0_m, sb_m, direct_m, tc_m = _split_merged(merged, side, N)
        with jax.named_scope("closure"):
            C = bool_closure(d0_m)
        with jax.named_scope("combine"):
            return _cache.combine_bool(direct_m, sb_m, tc_m, C)

    return jax.jit(sharded_rpq)



def _srcidx_own(fr: Fragmentation):
    """Host-side inverse of ``src_row``: for each fragment, the source-row
    index of every boundary position it owns (pad row ``S-1`` — the
    reserved s slot, never a real in-node row — elsewhere) plus the
    ownership mask.  [k, nb] each."""
    src_row = fr.arrays["src_row"]                         # [k, S]
    k, S, nb = fr.k, src_row.shape[1], fr.n_boundary
    srcidx = np.full((k, nb), S - 1, dtype=np.int32)
    own = np.zeros((k, nb), dtype=bool)
    for i in range(k):
        mine = src_row[i] < fr.B - 2
        srcidx[i, src_row[i, mine]] = np.nonzero(mine)[0]
        own[i, src_row[i, mine]] = True
    return srcidx, own


# inert pad values per fragment array: pad fragments must read as "no
# edges, no sources, no ownership" so their local stages converge in zero
# iterations and contribute only semiring zeros to the on-device merge
def _array_pads(fr: Fragmentation) -> dict:
    return dict(esrc=fr.n_max, edst=fr.n_max, src_local=fr.n_max,
                src_row=fr.B, tgt_local=fr.n_max, labels=-9, gids=-1,
                n_local=0)


# live entries in a Fragmentation's device-upload memo.  More than one
# because the MVCC store (core.versions) keeps several versions live and
# each version's repair re-uploads under a new arrays_version; a small LRU
# stops versions from thrashing each other's uploads while bounding device
# memory held by stale versions.
_UPLOAD_MEMO_CAP = 4


def _sharded(x: np.ndarray, mesh: Mesh) -> jax.Array:
    """Upload a device-major packed array straight into its shards: device
    ``i`` receives only rows ``[i*fpd, (i+1)*fpd)``, so the full stack never
    lands on one device before the jitted call reshards it."""
    return jax.device_put(x, NamedSharding(mesh, P(FRAG_AXIS)))


def _replicated(x: np.ndarray, mesh: Mesh) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P()))


def _device_inputs(fr: Fragmentation, placement: Placement,
                   mesh: Mesh) -> dict:
    """Query-independent device uploads for the batched sharded engines —
    the fragment arrays plus the boundary-ownership gathers, packed into
    the placement's device-major [d*fpd, ...] layout and placed shard by
    shard on ``mesh`` — memoized in a small per-Fragmentation LRU keyed on
    ``(fr.arrays_version, placement, mesh)`` so steady-state batches skip
    the host-to-device copy of the edge lists entirely; any
    ``apply_delta``/``rebuild`` (which mutates the host arrays in place and
    bumps the version) starts a fresh entry, as does switching placements.
    Several keys stay live so MVCC versions and alternate placements don't
    thrash each other's uploads."""
    memos = fr.__dict__.get("_sharded_device_inputs")
    if memos is None:
        memos = fr.__dict__["_sharded_device_inputs"] = OrderedDict()
    key = (fr.arrays_version, placement.cache_key(), mesh)
    memo = memos.get(key)
    if memo is not None:
        memos.move_to_end(key)
        return memo
    perm = placement.perm()
    pads = _array_pads(fr)
    srcidx, own = _srcidx_own(fr)
    mine = fr.boundary_owner()[None, :] == np.arange(fr.k)[:, None]
    mine[:, fr.nb_active:] = False     # spare slots are owned by nobody
    memo = dict(
        version=fr.arrays_version, placement=placement.cache_key(),
        perm=perm,
        arrs={key: _sharded(_pack_rows(v, perm, pads[key]), mesh)
              for key, v in fr.arrays.items()},
        srcidx=_sharded(_pack_rows(srcidx, perm, fr.s_max - 1), mesh),
        own=_sharded(_pack_rows(own, perm, False), mesh),
        mine=_sharded(_pack_rows(mine, perm, False), mesh),
        local_b=_replicated(fr.boundary_local(), mesh))
    memos[key] = memo
    while len(memos) > _UPLOAD_MEMO_CAP:
        memos.popitem(last=False)
    return memo


def _batch_sharded_program(fr: Fragmentation, pairs: np.ndarray, kind: str,
                           qa: Optional[QueryAutomaton] = None,
                           mesh: Optional[Mesh] = None,
                           placement: Optional[Placement] = None,
                           chaos=None):
    """(compiled-program, args) for one fused N-pair sharded batch of
    ``kind``.  All fragment data rides in as arguments, so one compiled
    program per (mesh, geometry, fragments-per-device, batch-bucket)
    serves every batch and stays valid across in-place graph deltas and
    re-placements."""
    mesh, placement = _resolve_placement(fr, mesh, placement)
    if chaos is not None:
        chaos.maybe_fail("upload")     # guards the _device_inputs transfer
    k, n_max, N = fr.k, fr.n_max, len(pairs)
    ss, tt = pairs[:, 0], pairs[:, 1]
    # per-fragment query inputs: [k, N] local slots of s and t (n_max
    # absent), packed below into the device-major layout
    s_slots = np.full((k, N), n_max, dtype=np.int32)
    s_slots[fr.part[ss], np.arange(N)] = fr.owner_local[ss]
    t_slots = fr.slot_index()[tt, :].T.copy()              # [k, N]
    dev = _device_inputs(fr, placement, mesh)
    perm, fpd = dev["perm"], placement.fpd
    s_slots = _sharded(_pack_rows(s_slots, perm, n_max), mesh)
    t_slots = _sharded(_pack_rows(t_slots, perm, n_max), mesh)
    arrs = dev["arrs"]
    if kind == "rpq":
        run = _batch_rpq_jitted(mesh, fr.n_boundary, n_max, fr.B,
                                qa.n_states, int(qa.start), fpd, N)
        args = (arrs["esrc"], arrs["edst"], arrs["src_local"],
                arrs["src_row"], arrs["tgt_local"], arrs["labels"],
                arrs["gids"], s_slots, t_slots,
                dev["mine"], _replicated(qa.state_labels, mesh),
                _replicated(qa.trans, mesh),
                _replicated(ss.astype(np.int32), mesh),
                _replicated(tt.astype(np.int32), mesh), dev["local_b"])
        return run, args
    jitted = {"reach": _batch_reach_jitted, "dist": _batch_dist_jitted}
    run = jitted[kind](mesh, fr.n_boundary, n_max, fpd, N)
    args = (arrs["esrc"], arrs["edst"], arrs["src_local"],
            arrs["tgt_local"], s_slots, t_slots,
            dev["srcidx"], dev["own"])
    return run, args


def _as_batch_pairs(pairs) -> np.ndarray:
    return np.asarray(pairs, dtype=np.int64).reshape(-1, 2)


def dis_reach_batch_sharded(fr: Fragmentation, pairs,
                            mesh: Optional[Mesh] = None,
                            placement: Optional[Placement] = None,
                            chaos=None) -> np.ndarray:
    """Answer N (s, t) pairs over the device mesh with a single collective.

    Each device contributes, for its owned fragments (one or several,
    per ``placement``): their rows of the boundary dependency matrix D0
    (all-sources local fixpoints), the s-row of every pair whose source
    they own, and the t-column entries of their own in-nodes — OR-merged
    on-device first, so the wire is identical to the one-fragment-per-
    device layout.  All three ride ONE bitpacked psum (== OR: every bit
    is computed on exactly one device); the closure and the per-pair
    combine run replicated.
    """
    pairs = _as_batch_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=bool)
    with tracing.span("repro.session.inputs"):
        run, args = _batch_sharded_program(fr, pairs, "reach", mesh=mesh,
                                           placement=placement, chaos=chaos)
    if chaos is not None:
        chaos.maybe_fail("engine.shard_map", pairs=pairs)
    with tracing.span("repro.session.device"):
        ans = np.array(run(*args))
    ans[pairs[:, 0] == pairs[:, 1]] = True
    return ans


def dis_dist_batch_sharded(fr: Fragmentation, pairs,
                           mesh: Optional[Mesh] = None,
                           placement: Optional[Placement] = None,
                           chaos=None) -> np.ndarray:
    """Tropical twin of :func:`dis_reach_batch_sharded`: N shortest
    distances with ONE int32 pmin collective (W0 rows + per-pair tropical
    s-rows and t-columns; a device's owned fragments min-merge on-device
    first).  Returns [N] int64 with -1 for unreachable — the same
    contract as the host ``cache.dis_dist_batch``."""
    pairs = _as_batch_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=np.int64)
    with tracing.span("repro.session.inputs"):
        run, args = _batch_sharded_program(fr, pairs, "dist", mesh=mesh,
                                           placement=placement, chaos=chaos)
    if chaos is not None:
        chaos.maybe_fail("engine.shard_map", pairs=pairs)
    with tracing.span("repro.session.device"):
        d = np.asarray(run(*args)).astype(np.int64)
    d[d >= int(engine.INF)] = -1
    return d


def dis_rpq_batch_sharded(fr: Fragmentation, pairs, qa: QueryAutomaton,
                          mesh: Optional[Mesh] = None,
                          placement: Optional[Placement] = None,
                          chaos=None) -> np.ndarray:
    """Product-automaton twin of :func:`dis_reach_batch_sharded` for one
    automaton: each device ships its owned fragments' product rvset rows
    plus N forward / reverse product propagations' contributions in ONE
    bitpacked psum; the (nb|Q|)^2 closure and combine run replicated.
    Returns [N] bool (s == t answered by nullability, like
    ``cache.dis_rpq_batch``)."""
    pairs = _as_batch_pairs(pairs)
    if len(pairs) == 0:
        return np.zeros(0, dtype=bool)
    with tracing.span("repro.session.inputs"):
        run, args = _batch_sharded_program(fr, pairs, "rpq", qa=qa,
                                           mesh=mesh, placement=placement,
                                           chaos=chaos)
    if chaos is not None:
        chaos.maybe_fail("engine.shard_map", pairs=pairs)
    with tracing.span("repro.session.device"):
        ans = np.array(run(*args))
    ans[pairs[:, 0] == pairs[:, 1]] = bool(qa.nullable)
    return ans


def lower_batch_hlo(fr: Fragmentation, pairs, kind: str,
                    qa: Optional[QueryAutomaton] = None,
                    mesh: Optional[Mesh] = None,
                    placement: Optional[Placement] = None) -> str:
    """Lowered HLO text of one fused sharded batch of ``kind`` — used by
    tests to assert the one-collective-per-group guarantee and the payload
    dtype/shape structurally, for all three query classes (including
    packed d < k placements)."""
    pairs = _as_batch_pairs(pairs)
    run, args = _batch_sharded_program(fr, pairs, kind, qa=qa, mesh=mesh,
                                       placement=placement)
    return run.lower(*args).as_text()


# ---------------------------------------------------------------------------
# sharded incremental cache maintenance (DESIGN.md Sec. 3.5)
# ---------------------------------------------------------------------------

def _changed_row_inputs(fr: Fragmentation, row_ids: np.ndarray):
    """Per-device gather indices for the changed boundary rows: for each
    fragment, the source-row index of every changed position it owns
    (pad ``s_max-1`` — the reserved s slot, never a real in-node row —
    elsewhere) plus the ownership mask."""
    k, S = fr.k, fr.s_max
    src_row = fr.arrays["src_row"]                         # [k, S]
    srcidx = np.full((k, len(row_ids)), S - 1, dtype=np.int32)
    own = np.zeros((k, len(row_ids)), dtype=bool)
    inv = {}
    for f in range(k):
        for j in np.nonzero(src_row[f] < fr.B - 2)[0]:
            inv[int(src_row[f, j])] = (f, int(j))
    for c, r in enumerate(row_ids):
        f, j = inv[int(r)]
        srcidx[f, c] = j
        own[f, c] = True
    return srcidx, own


@functools.lru_cache(maxsize=32)
def _update_rows_jitted(mesh: Mesh, nb: int, n_max: int, fpd: int):
    """Compiled-program cache for the sharded update: one entry per
    (mesh, boundary, slot, fragments-per-device) geometry; jit then caches
    per changed-row bucket shape, so steady-state deltas never retrace."""
    in_specs = tuple(P(FRAG_AXIS) for _ in range(6))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(), P(FRAG_AXIS)))
    def sharded_delta(esrc, edst, init, srcidx, own, tgt_local):
        # [fpd, ...] per device: resume every owned fragment's fixpoint
        # (fragments untouched by the delta — including ones co-packed
        # with a dirty neighbour — start at fixpoint and converge in one
        # relaxation; inert pads converge in zero)
        def one(Ff, sidx, ownf, tloc):
            rows = jnp.take(Ff, sidx, axis=0)              # [r, n+1]
            return jnp.take(rows, tloc[:nb], axis=1) & ownf[:, None]

        with jax.named_scope("local_stage"):
            F = jax.vmap(functools.partial(
                engine.resume_frontier_reach, n_max=n_max))(
                esrc, edst, init)                          # [fpd, S, n+1]
            d0r = jnp.any(jax.vmap(one)(F, srcidx, own, tgt_local), axis=0)
        # the ONE update collective: changed rows only, bitpacked (pmax ==
        # OR: each row is owned by exactly one device, others ship zeros)
        with jax.named_scope("collective"):
            merged = unpack_payload(
                jax.lax.pmax(pack_payload(d0r), FRAG_AXIS), nb)
        return merged, F

    return jax.jit(sharded_delta)


def _update_rows_program(fr: Fragmentation, warm_init: np.ndarray,
                         row_ids: np.ndarray, mesh: Mesh,
                         placement: Placement):
    perm = placement.perm()
    srcidx, own = _changed_row_inputs(fr, row_ids)
    dev = _device_inputs(fr, placement, mesh)
    arrs = (dev["arrs"]["esrc"], dev["arrs"]["edst"],
            _sharded(_pack_rows(np.asarray(warm_init), perm, False), mesh),
            _sharded(_pack_rows(srcidx, perm, fr.s_max - 1), mesh),
            _sharded(_pack_rows(own, perm, False), mesh),
            dev["arrs"]["tgt_local"])
    return (_update_rows_jitted(mesh, fr.n_boundary, fr.n_max,
                                placement.fpd), arrs)


def _unpack_rows(packed: np.ndarray, perm: np.ndarray, k: int) -> np.ndarray:
    """Invert :func:`_pack_rows`: device-major [d*fpd, ...] back to the
    stacked per-fragment [k, ...] order (pad slots dropped)."""
    valid = perm >= 0
    out = np.zeros((k,) + packed.shape[1:], dtype=packed.dtype)
    out[perm[valid]] = packed[valid]
    return out


def update_rows_sharded(fr: Fragmentation, warm_init: np.ndarray,
                        row_ids: np.ndarray, mesh: Optional[Mesh] = None,
                        placement: Optional[Placement] = None):
    """Recompute the changed D0 rows over the device mesh.

    Every device resumes its owned fragments' all-sources fixpoints from
    ``warm_init`` (clean fragments are already at fixpoint and converge in
    one relaxation), then contributes the rows of ``row_ids`` it owns.
    The ONE collective ships only the *changed* bitpacked rows —
    ``len(row_ids) x ceil(nb/32)`` uint32 words, not the whole matrix.

    Returns ``(rows, frontiers)``: the merged [r, nb] changed rows and the
    per-fragment [k, S, n_max+1] frontiers (sharded outputs unpacked from
    the device-major layout, no extra communication), both on the default
    device, where the host rvset cache they update lives.
    """
    mesh, placement = _resolve_placement(fr, mesh, placement)
    run, arrs = _update_rows_program(fr, warm_init, row_ids, mesh,
                                     placement)
    rows, fronts = run(*arrs)
    # both feed the host rvset cache, which lives on one device: a program
    # mixing them with it while they still span the mesh would have to
    # partition the cache's Pallas kernels, which Mosaic cannot do
    fronts = _unpack_rows(np.asarray(fronts), placement.perm(), fr.k)
    return jnp.asarray(np.asarray(rows)), jnp.asarray(fronts)


def lower_update_hlo(fr: Fragmentation, warm_init: np.ndarray,
                     row_ids: np.ndarray,
                     mesh: Optional[Mesh] = None,
                     placement: Optional[Placement] = None) -> str:
    """Lowered HLO of the sharded cache-update program — used by tests to
    assert the changed-rows-only payload structurally."""
    mesh, placement = _resolve_placement(fr, mesh, placement)
    run, arrs = _update_rows_program(fr, warm_init, row_ids, mesh,
                                     placement)
    return run.lower(*arrs).as_text()


def apply_delta_sharded(fr: Fragmentation, delta, mesh: Optional[Mesh] = None,
                        placement: Optional[Placement] = None, chaos=None):
    """Sharded twin of :func:`repro.core.incremental.apply_delta` for
    insert-only deltas against a reach cache: each fragment's frontier
    resume runs on its owning device (dirty fragments co-packed with
    clean ones only redo their own fixpoint) and the update collective
    ships only the changed bitpacked D0 rows; the rank-style closure
    update runs replicated (exactly like evalDG).  Deletions, rebuilds,
    and tropical caches fall back to the host path.

    Like the host path, the ``delta.repair`` chaos site fires *after* the
    host arrays mutate — rollback is the caller's job.
    """
    from . import incremental
    from .cache import _boundary_rows, get_rvset_cache

    cache = get_rvset_cache(fr)
    if (delta.is_empty() or delta.n_del or cache.bl_dist is not None):
        return incremental.apply_delta(fr, delta, chaos=chaos)
    warm = np.zeros((fr.k, fr.s_max, fr.n_max + 1), dtype=bool)
    bl_host = np.asarray(cache.bl_frontier)
    report = fr.apply_delta(delta)
    if chaos is not None:
        chaos.maybe_fail("delta.repair")
    if report.rebuilt:
        return incremental.rebuild_cache(fr, cache.version, report,
                                         with_dist=False,
                                         reason=report.reason)
    for f in range(fr.k):
        init, _, _ = incremental._frontier_init(fr, f, bl_host, dist=False)
        warm[f] = np.asarray(init)
    row_ids = incremental.changed_row_ids(fr, report.dirty)
    if row_ids.size == 0:      # dirty fragments own no boundary rows:
        incremental._update_frontiers(cache, report.dirty, warm=True)
        cache.refresh_device_arrays(incremental.touched_arrays(report))
        return incremental.UpdateStats(mode="repair_sharded",
                                       **incremental._stats_base(report))
    padded = incremental.pad_row_ids(row_ids, cap=fr.n_boundary)
    rows_new, fronts = update_rows_sharded(fr, warm, padded, mesh=mesh,
                                           placement=placement)
    cache.bl_frontier = _boundary_rows(fr, fronts, False,
                                       lambda ref, v: ref.max(v))
    cache.closure = incremental._rank_update_bool(cache.closure, rows_new,
                                                  padded)
    cache.refresh_device_arrays(incremental.touched_arrays(report))
    return incremental.UpdateStats(mode="repair_sharded",
                                   changed_rows=int(row_ids.size),
                                   **incremental._stats_base(report))
