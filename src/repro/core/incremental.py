"""Incremental rvset-cache maintenance for dynamic graphs (DESIGN.md Sec. 3.5).

The paper's guarantees hold for a *static* fragmentation; the serving engine
amortizes work across queries precisely because real workloads re-query one
graph — and real graphs change between queries.  This module keeps the cached
structures of :mod:`repro.core.cache` valid under edge updates without
recomputing them from scratch:

* **insertions** are monotone, so the cached state is reusable twice over:
  the affected fragment's all-sources fixpoint is *resumed* from the cached
  frontiers (``engine.resume_frontier_*`` converges in O(new-path-length)
  relaxations instead of O(diam)), and the changed rows of the boundary
  matrix ``D0`` are pushed through the cached closure with a rank-style
  semiring update — a closure over the r x r changed-row block instead of
  the full |V_f| x |V_f| matrix (``_rank_update_bool`` / ``_tropical``,
  riding the same ``or_and_matmul`` / ``min_plus_matmul`` dispatchers);
* **cross-edge insertions** grow ``V_f`` into the pre-allocated spare
  boundary slots of :func:`repro.core.fragments.fragment_graph`
  (``reserve_boundary``), so every device array keeps its shape and nothing
  retraces;
* **deletions** are not monotone, so the dirty fragments' frontiers are
  recomputed cold and the closure rebuilt from the (mostly cached) ``D0``;
  a debt counter decides when enough deletions have accumulated that a full
  structural rebuild (which also compacts stale boundary slots and stubs)
  is cheaper than continuing to repair.

Correctness of the rank-style update: let ``R`` be the changed rows and
``T = D0'[R] (x) C`` (one possibly-new hop out of R, then old paths).  Any
path in the updated dependency graph decomposes at its uses of R-row edges
into  ``u --C--> r_1 --T--> r_2 --T--> ... --T--> v``,  so with
``M = T[:, R]`` and ``M*`` its closure,

    C' = C  |  C[:, R] (x) M* (x) T          (boolean; min-plus analogous)

— exact for monotone updates because old entries stay valid lower bounds.
The changed-row count is padded to ``ROW_PAD`` buckets so repeated repairs
reuse compiled programs.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import bes, engine
from .cache import _gather_boundary_matrix, prepare_rvset_cache
from .engine import INF
from .fragments import Fragmentation, GraphDelta

ROW_PAD = 64                 # changed-row padding bucket (jit stability)
RECOMPUTE_DIRTY_FRAC = 0.5   # most fragments dirty -> recompute beats repair
DEBT_PER_RECOMPUTE = 0.5     # deletion-recompute cost, in full-rebuild units
REBUILD_DEBT = 4.0           # accumulated debt that triggers a full rebuild


@dataclasses.dataclass
class UpdateStats:
    """What one :func:`apply_delta` call did to the fragmentation + cache."""

    mode: str                # noop | structural | repair | recompute | rebuild
    n_add_intra: int = 0
    n_add_cross: int = 0
    n_del: int = 0
    dirty_fragments: int = 0
    new_boundary: int = 0
    changed_rows: int = 0
    reason: str = ""


def _stats_base(report) -> dict:
    return dict(n_add_intra=report.n_add_intra,
                n_add_cross=report.n_add_cross, n_del=report.n_del,
                dirty_fragments=int(report.dirty.sum()),
                new_boundary=len(report.new_boundary))


# fragment arrays a cross-edge insertion can mutate beyond the edge lists:
# _ensure_boundary touches src_local/src_row, _ensure_stub touches
# gids/labels/tgt_local/n_local (fragments.Fragmentation.apply_delta)
_CROSS_TOUCHED = ("src_local", "src_row", "gids", "labels", "tgt_local",
                  "n_local")


def touched_arrays(report) -> set:
    """``fr.arrays`` keys the applied delta mutated, from its
    :class:`~repro.core.fragments.DeltaReport` — what
    :meth:`RvsetCache.refresh_device_arrays` needs to re-upload.
    Intra-fragment edges and deletions only rewrite the edge lists; cross
    insertions additionally grow stubs/sources (see ``_CROSS_TOUCHED``)."""
    names = {"esrc", "edst"}
    if report.n_add_cross:
        names.update(_CROSS_TOUCHED)
    return names


def rebuild_cache(fr: Fragmentation, old_version: int, report,
                  with_dist: bool, reason: str = "") -> UpdateStats:
    """Drop + rebuild the cache from the current fragmentation state.
    Snapshot ids stay monotone across rebuilds (QueryServer stamps answers
    with ``cache.version``).  Shared by the host and sharded update paths."""
    fr.rvset_cache = None
    fresh = prepare_rvset_cache(fr, with_dist=with_dist)
    fresh.version = old_version + 1
    return UpdateStats(mode="rebuild", reason=reason, **_stats_base(report))


def apply_delta(fr: Fragmentation, delta: GraphDelta,
                chaos=None) -> UpdateStats:
    """Apply ``delta`` to ``fr`` and incrementally repair its rvset cache.

    The attached cache (if any) answers identically to one rebuilt from
    scratch afterwards — pinned property-style by tests/test_incremental.py.
    An empty delta is a strict no-op (cached arrays keep their identity).

    ``chaos`` (a :class:`repro.serve.faults.FaultInjector`) is consulted at
    the ``delta.repair`` site *after* the host arrays have mutated, so an
    injected failure leaves the fragmentation genuinely mid-update — the
    caller (``QuerySession.apply``) is responsible for rolling back via
    :meth:`Fragmentation.snapshot` / ``restore``.
    """
    if delta.is_empty():
        return UpdateStats(mode="noop")
    cache = fr.rvset_cache
    with_dist = cache is not None and cache.bl_dist is not None
    report = fr.apply_delta(delta)
    if chaos is not None:
        chaos.maybe_fail("delta.repair")
    base = _stats_base(report)
    if cache is None:
        return UpdateStats(mode="structural", **base)
    if report.rebuilt:
        return rebuild_cache(fr, cache.version, report, with_dist,
                             reason=report.reason)

    dirty_frac = float(report.dirty.mean())
    if report.n_del:
        cache.repair_debt += DEBT_PER_RECOMPUTE + 0.5 * dirty_frac
        if cache.repair_debt >= REBUILD_DEBT:
            fr.rebuild()
            return rebuild_cache(fr, cache.version, report, with_dist,
                                 reason="repair debt")
        _recompute(cache, report.dirty, warm=False)
        cache.refresh_device_arrays(touched_arrays(report))
        return UpdateStats(mode="recompute", **base)
    if dirty_frac > RECOMPUTE_DIRTY_FRAC:
        # insert-only but wide: the changed-row block is most of the matrix,
        # so a (warm-started) recompute is cheaper than the rank update
        _recompute(cache, report.dirty, warm=True)
        cache.refresh_device_arrays(touched_arrays(report))
        return UpdateStats(mode="recompute", **base)
    changed = _repair_insert(cache, report.dirty)
    cache.refresh_device_arrays(touched_arrays(report))
    return UpdateStats(mode="repair", changed_rows=changed, **base)


# ---------------------------------------------------------------------------
# frontier maintenance (per-fragment, warm- or cold-started)
# ---------------------------------------------------------------------------

def _frontier_init(fr: Fragmentation, f: int, warm_rows, dist: bool):
    """[S, n_max+1] initial state for fragment ``f``'s all-sources fixpoint:
    the cached boundary rows when warm (insert-only deltas — the old
    fixpoint is a valid starting bound), plain seeds when cold."""
    src_local = fr.arrays["src_local"][f]
    src_row = fr.arrays["src_row"][f]
    valid = src_row < fr.B - 2
    rows = np.nonzero(valid)[0]
    shape = (fr.s_max, fr.n_max + 1)
    if dist:
        init = np.full(shape, int(INF), dtype=np.int32)
        if warm_rows is not None:
            init[rows] = warm_rows[src_row[valid]]
        init[rows, src_local[valid]] = 0
    else:
        init = np.zeros(shape, dtype=bool)
        if warm_rows is not None:
            init[rows] = warm_rows[src_row[valid]]
        init[rows, src_local[valid]] = True
    return jnp.asarray(init), rows, src_row[valid]


def _update_frontiers(cache, dirty: np.ndarray, warm: bool):
    """Re-run the all-sources fixpoint of every dirty fragment and scatter
    the refreshed rows back into the cached [nb, n_max+1] matrices."""
    fr = cache.fr
    bl, bl_d = cache.bl_frontier, cache.bl_dist
    bl_host = np.asarray(bl)
    bl_d_host = np.asarray(bl_d) if bl_d is not None else None
    for f in np.nonzero(dirty)[0]:
        esrc = jnp.array(fr.arrays["esrc"][f])
        edst = jnp.array(fr.arrays["edst"][f])
        init, rows, bpos = _frontier_init(
            fr, f, bl_host if warm else None, dist=False)
        front = engine.resume_frontier_reach(esrc, edst, init,
                                             n_max=fr.n_max)
        bl = bl.at[jnp.asarray(bpos)].set(front[jnp.asarray(rows)])
        if bl_d is not None:
            init_d, rows, bpos = _frontier_init(
                fr, f, bl_d_host if warm else None, dist=True)
            front_d = engine.resume_frontier_dist(esrc, edst, init_d,
                                                  n_max=fr.n_max)
            bl_d = bl_d.at[jnp.asarray(bpos)].set(front_d[jnp.asarray(rows)])
    cache.bl_frontier = bl
    if bl_d is not None:
        cache.bl_dist = bl_d


# ---------------------------------------------------------------------------
# closure maintenance: rank-style update (inserts) / rebuild (deletes)
# ---------------------------------------------------------------------------

def changed_row_ids(fr: Fragmentation, dirty: np.ndarray) -> np.ndarray:
    """Active boundary positions whose D0 row may have changed: exactly the
    in-nodes owned by dirty fragments (stubs — and hence row reads — of a
    fragment only change when its own edge list does)."""
    owner = fr.boundary_owner()
    mask = dirty[owner]
    mask[fr.nb_active:] = False            # spare slots own no rows
    return np.nonzero(mask)[0]


def pad_row_ids(row_ids: np.ndarray, pad: int = ROW_PAD,
                cap: int = None) -> np.ndarray:
    """Pad the changed-row set to a bucket size by repeating the first id —
    duplicate rows are semiring no-ops (identical constraints OR/min twice)
    and keep the repair kernels' shapes in a small set of buckets.  ``cap``
    (the matrix side) bounds the bucket so a small boundary never pays for
    more rows than the full matrix has."""
    r = len(row_ids)
    rp = ((r + pad - 1) // pad) * pad
    if cap is not None:
        rp = min(rp, max(cap, r))
    return np.concatenate([row_ids, np.full(rp - r, row_ids[0], np.int64)])


def gather_rows(fr: Fragmentation, bl, row_ids: np.ndarray):
    """D0 rows ``row_ids`` read out of frontier matrix ``bl`` (the gather
    of cache._gather_boundary_matrix, restricted to the changed rows; the
    pad column carries the semiring zero, so spare targets read inert)."""
    nb = fr.n_boundary
    owner = fr.boundary_owner()
    cols = fr.arrays["tgt_local"][owner[row_ids]][:, :nb]
    rows = bl[jnp.asarray(row_ids)]
    return jnp.take_along_axis(rows, jnp.asarray(cols), axis=1)


@jax.jit
def _rank_update_bool(C, rows_new, idx):
    """C' = C | C[:, R] (x) closure(T[:, R]) (x) T with T = rows_new (x) C;
    exact for monotone row updates (see module docstring).  One jitted
    program per changed-row bucket size."""
    from ..kernels.bool_matmul.ops import or_and_matmul
    T = or_and_matmul(rows_new, C)                     # [r, nb]
    Mc = bes.bool_closure(T[:, idx])
    left = or_and_matmul(C[:, idx], Mc)                # [nb, r]
    return C | or_and_matmul(left, T)


@jax.jit
def _rank_update_tropical(Cd, rows_new, idx):
    from ..kernels.tropical_matmul.ops import min_plus_matmul
    T = jnp.minimum(min_plus_matmul(rows_new, Cd), INF)
    Mc = bes.tropical_closure(T[:, idx])
    left = jnp.minimum(min_plus_matmul(Cd[:, idx], Mc), INF)
    via = jnp.minimum(min_plus_matmul(left, T), INF)
    return jnp.minimum(Cd, via)


def _repair_insert(cache, dirty: np.ndarray) -> int:
    """Insert-only repair: warm frontier resume + rank-style closure update.

    The candidate rows (every in-node of a dirty fragment) are diffed
    against the pre-update frontiers and only rows whose D0 entries
    *actually changed* go through the closure update — in a dense fragment
    most insertions change few or no boundary rows, so the common case is
    a cheap frontier resume and a no-op (or tiny) rank update.  Returns the
    number of changed D0 rows pushed through the closure."""
    fr = cache.fr
    bl_old, bl_d_old = cache.bl_frontier, cache.bl_dist
    _update_frontiers(cache, dirty, warm=True)
    candidates = changed_row_ids(fr, dirty)
    if fr.n_boundary == 0 or candidates.size == 0:
        return 0
    # diff candidate D0 rows old vs new (new stub columns read all-false /
    # INF out of the old frontiers, so freshly activated rows always diff)
    rows_new = gather_rows(fr, cache.bl_frontier, candidates)
    rows_old = gather_rows(fr, bl_old, candidates)
    changed = np.any(np.asarray(rows_new != rows_old), axis=1)
    rows_d_new = rows_d_old = None
    if cache.bl_dist is not None:
        rows_d_new = gather_rows(fr, cache.bl_dist, candidates)
        rows_d_old = gather_rows(fr, bl_d_old, candidates)
        changed |= np.any(np.asarray(rows_d_new != rows_d_old), axis=1)
    if not changed.any():
        return 0
    sel = np.nonzero(changed)[0]
    padded_sel = pad_row_ids(sel, cap=fr.n_boundary)
    padded = candidates[padded_sel]
    cache.closure = _rank_update_bool(cache.closure, rows_new[padded_sel],
                                      jnp.asarray(padded))
    if cache.bl_dist is not None:
        cache.dist_closure = _rank_update_tropical(
            cache.dist_closure, rows_d_new[padded_sel], jnp.asarray(padded))
    return int(sel.size)


def _recompute(cache, dirty: np.ndarray, warm: bool):
    """Per-fragment recompute: refresh dirty fragments' frontiers (cold
    when deletions are present — the old state over-approximates), then
    rebuild D0 by gather and re-close it.  Clean fragments' frontier rows —
    the expensive part — are reused as-is."""
    fr = cache.fr
    _update_frontiers(cache, dirty, warm=warm)
    D0 = _gather_boundary_matrix(fr, cache.bl_frontier, fill=False)
    cache.closure = bes.bool_closure(D0)
    if cache.bl_dist is not None:
        W0 = _gather_boundary_matrix(fr, cache.bl_dist, fill=INF)
        cache.dist_closure = bes.tropical_closure(W0)
