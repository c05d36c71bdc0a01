"""QuerySession: one handle over a (dynamic) fragmentation for all three
query classes (DESIGN.md Sec. 5).

``repro.connect(fr)`` opens a session that owns the amortized caches
(rvset / tropical / per-automaton product closures, physically attached to
the Fragmentation so every view of it shares one copy), the backend choice
(single-host ``vmap`` vs ``shard_map``, which packs the ``k`` fragments
onto a mesh of ``d <= k`` devices per a
:class:`~repro.core.fragments.Placement`), snapshot version stamping, and
delta application.  ``session.run([...])`` takes a
heterogeneous batch of :mod:`repro.core.plan` IR values, groups it by
(kind, automaton) through the planner, and serves every group with ONE
compiled batched execution — reach and dist through the PR-2 kernels, RPQs
through the batched product-closure path — returning
:class:`~repro.core.plan.QueryResult`\\ s in submission order.

The seed free functions (``dis_reach``, ``dis_dist``, ``dis_rpq``) are
thin shims over per-fragmentation default sessions (see ``core.api``);
everything inside ``src/repro`` talks to the session directly.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from . import cache as _cache
from . import engine, incremental
from ..errors import DeltaApplyFailed, Status
from .automaton import QueryAutomaton, build_query_automaton
from .engine import INF, QueryStats
from .fragments import Fragmentation, GraphDelta, Placement, query_slots
from .plan import (Dist, ExecutionGroup, Query, QueryPlan, QueryResult,
                   Reach, Rpq, plan_queries)

BACKENDS = ("auto", "vmap", "shard_map")
CACHE_MODES = ("amortized", "none")


@dataclasses.dataclass
class SessionStats:
    """Work accounting across the session's lifetime."""

    queries: int = 0         # queries answered
    batches: int = 0         # run() calls
    executions: int = 0      # compiled-program invocations issued
    updates: int = 0         # deltas applied
    # robustness accounting (DESIGN.md Sec. 7)
    degraded_groups: int = 0  # sharded groups served by the vmap fallback
    rollbacks: int = 0        # failed deltas rolled back to their snapshot
    # rows of the cached groups' programs: the queries, and the bucket rows
    # the planner padded them to (the kernels' own padding is not counted)
    rows_useful: int = 0
    rows_padded: int = 0
    # reach reads the cached groups answered, and of those the ones a dist
    # group answered from its distances (the planner's reach_in_dist)
    reach_rows: int = 0
    reach_fused: int = 0
    # fragment blocks the cached vmap reach/dist groups relaxed their rows
    # over (cache.fragment_blocks): rows_useful / frag_blocks rows share
    # one edge list
    frag_blocks: int = 0


def connect(fr: Fragmentation, backend: str = "auto",
            cache: str = "amortized", mesh=None,
            placement: Optional[Placement] = None,
            chaos=None) -> "QuerySession":
    """Open a :class:`QuerySession` over ``fr`` — the front door of the
    library (also exported as ``repro.connect``).

    ``backend``:

    * ``"vmap"`` runs every fragment's localEval as one SPMD program on
      the host device;
    * ``"shard_map"`` distributes the fragments over the devices of
      ``mesh`` (built lazily when omitted) according to ``placement``
      and keeps the one-collective guarantee per fused batch for all
      three query classes.  Meshes *smaller* than ``fr.k`` are valid —
      each device packs several fragments (``k >> d`` scale-out); meshes
      larger than ``fr.k`` are refused (a fragment is never split);
    * ``"auto"`` picks shard_map whenever more than one device is
      available and ``d <= fr.k`` (judged against ``mesh`` when one is
      passed), and vmap otherwise.

    ``placement`` maps fragment -> device (see
    :class:`~repro.core.fragments.Placement`); when omitted the session
    uses greedy workload balancing (``Placement.balanced``) over the mesh
    size.  ``cache``: ``"amortized"`` serves batches from the
    rvset/product caches (built lazily, shared with every other session
    on the same fragmentation); ``"none"`` evaluates each query with the
    seed one-shot engine and never builds cache state.

    ``chaos``: an optional :class:`repro.serve.faults.FaultInjector`
    consulted at every engine / upload / delta-repair site — the handle
    tests and benchmarks use to exercise the failure paths of
    DESIGN.md Sec. 7.  ``None`` (the default) adds zero overhead.
    """
    return QuerySession(fr, backend=backend, cache=cache, mesh=mesh,
                        placement=placement, chaos=chaos)


class QuerySession:
    """Unified query interface over one fragmentation (see :func:`connect`)."""

    def __init__(self, fr: Fragmentation, backend: str = "auto",
                 cache: str = "amortized", mesh=None,
                 placement: Optional[Placement] = None, chaos=None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of "
                             f"{BACKENDS}")
        if cache not in CACHE_MODES:
            raise ValueError(f"unknown cache mode {cache!r}; expected one "
                             f"of {CACHE_MODES}")
        self.fr = fr
        self.cache_mode = cache
        self._mesh = mesh
        if placement is not None and placement.k != fr.k:
            raise ValueError(f"placement maps {placement.k} fragments but "
                             f"the fragmentation has {fr.k}")
        if placement is not None and mesh is not None \
                and mesh.devices.size != placement.d:
            raise ValueError(f"mesh has {mesh.devices.size} devices but "
                             f"the placement expects {placement.d}")
        # d: the device budget the sharded backend would run on.  An
        # explicit placement or mesh pins it; otherwise every process
        # device up to fr.k is used (fragments pack when devices < k).
        # shard_map fits iff d <= fr.k — a fragment is never split across
        # devices, so a mesh LARGER than fr.k is refused.
        if placement is not None:
            d = placement.d
            have = f"a {d}-device placement"
        elif mesh is not None:
            d = int(mesh.devices.size)
            have = f"a {d}-device mesh"
        else:
            d = min(len(jax.devices()), fr.k)
            have = f"{len(jax.devices())} devices"
        fits = 1 <= d <= fr.k
        if backend == "auto":
            backend = "shard_map" if fr.k > 1 and d > 1 and fits else "vmap"
        elif backend == "shard_map" and not fits:
            raise ValueError(
                f"backend='shard_map' packs fragments onto at most one "
                f"device each ({fr.k} fragments), cannot use {have}; pass "
                f"a mesh/placement with <= {fr.k} devices, or "
                "backend='auto' to fall back to vmap")
        self.backend = backend
        if backend == "shard_map" and placement is None:
            placement = Placement.balanced(fr, d)
        self.placement = placement
        self.chaos = chaos
        self.stats = SessionStats()
        self.last_plan: Optional[QueryPlan] = None
        self._regex_cache: Dict[str, QueryAutomaton] = {}
        # serializes group execution and delta application so several
        # server threads can share one session over the same caches; an
        # RLock because run() resolves automatons (also locked) inline
        self._lock = threading.RLock()

    # -- cache lifecycle ---------------------------------------------------

    def warm(self, with_dist: bool = False) -> "QuerySession":
        """Eagerly build the amortized caches (no-op for cache='none')."""
        with self._lock:
            if self.cache_mode == "amortized":
                _cache.prepare_rvset_cache(self.fr, with_dist=with_dist)
        return self

    @property
    def cache_version(self) -> Optional[int]:
        """Snapshot id of the attached rvset cache (None before first
        build or for uncached sessions); bumped by every delta repair."""
        c = self.fr.rvset_cache
        return None if c is None else c.version

    # -- dynamic graphs ----------------------------------------------------

    def apply(self, delta: GraphDelta) -> incremental.UpdateStats:
        """Apply a :class:`GraphDelta` and repair the session's caches in
        place (DESIGN.md Sec. 3.5).  On the shard_map backend the repair
        collective ships only the changed bitpacked rows; otherwise (and
        for the cases the sharded path does not cover) the host repair
        runs.  Queries run after this see the new snapshot
        (``cache_version`` is bumped).

        The host cache is repaired even though sharded *answers* recompute
        on-device: it stays the ``cache_version`` snapshot source and is
        shared with vmap sessions/shims on this fragmentation, which would
        otherwise read stale state (DESIGN.md Sec. 5, known trade-off).

        A delta that fails mid-apply (bad input, engine failure, injected
        chaos) is **rolled back**: the fragmentation and its caches return
        to the pre-delta snapshot (``arrays_version`` / ``cache_version``
        unchanged, subsequent queries answer against the pre-delta graph)
        and a typed :class:`~repro.errors.DeltaApplyFailed` wrapping the
        cause is raised (DESIGN.md Sec. 7)."""
        with self._lock:
            self.stats.updates += 1
            snap = self.fr.snapshot()
            try:
                if (self.backend == "shard_map"
                        and self.fr.rvset_cache is not None):
                    from . import distributed
                    return distributed.apply_delta_sharded(
                        self.fr, delta, mesh=self._mesh,
                        placement=self.placement, chaos=self.chaos)
                return incremental.apply_delta(self.fr, delta,
                                               chaos=self.chaos)
            except Exception as exc:
                self.fr.restore(snap)
                self.stats.rollbacks += 1
                raise DeltaApplyFailed(exc) from exc

    def repair_on(self, fr: Fragmentation,
                  delta: GraphDelta) -> incremental.UpdateStats:
        """Repair ``fr``'s caches for ``delta`` — the MVCC building block
        (:mod:`repro.core.versions`).  Unlike :meth:`apply` this neither
        takes the session lock nor snapshots: ``fr`` is a private
        copy-on-write clone that no reader can see, so the repair runs
        concurrently with queries against the head version, and a failed
        repair is handled by *dropping* the clone (the head was never
        touched) rather than restoring a snapshot."""
        self.stats.updates += 1
        if self.backend == "shard_map" and fr.rvset_cache is not None:
            from . import distributed
            return distributed.apply_delta_sharded(
                fr, delta, mesh=self._mesh, placement=self.placement,
                chaos=self.chaos)
        return incremental.apply_delta(fr, delta, chaos=self.chaos)

    # -- query execution ---------------------------------------------------

    def run(self, queries: Union[Query, Sequence[Query]],
            version=None) -> List[QueryResult]:
        """Answer a heterogeneous batch; results in submission order.

        The batch is grouped by (kind, automaton) and each group is served
        by one compiled batched execution (``cache='amortized'``) or by
        per-query seed evaluations (``cache='none'``).  Every result is
        stamped with the cache snapshot it was computed against.

        ``version``: an optional pinned MVCC :class:`~repro.core.versions.
        Version` — the batch then runs against that snapshot's
        fragmentation and cache instead of ``self.fr``, and results are
        stamped with *its* ``cache_version``.  This is how the async
        engine serves reads while the next version repairs concurrently.

        Thread-safe: the whole batch runs under the session lock, so a
        concurrent :meth:`apply` can never move the snapshot between a
        group's execution and its ``cache_version`` stamp.  (MVCC repairs
        hold the lock only for the copy-on-write clone, never for the
        repair itself — see :meth:`repair_on` — so versioned batches wait
        at most one memcpy, never a repair.)
        """
        if isinstance(queries, (Reach, Dist, Rpq)):
            queries = [queries]
        queries = list(queries)
        fr = self.fr if version is None else version.fr
        with tracing.span("repro.session.run", n=len(queries)), self._lock:
            with tracing.span("repro.session.plan"):
                plan = plan_queries(queries, self._resolve_automaton,
                                    reach_in_dist=self._reach_in_dist(fr))
            self.last_plan = plan
            results: List[Optional[QueryResult]] = [None] * len(queries)
            for group in plan.groups:
                with tracing.span("repro.session.group", kind=group.kind,
                                  n=group.n, bucket=group.padded_size,
                                  reach=group.n_reach,
                                  frags=self._frag_blocks(fr, group)):
                    if self.cache_mode == "amortized":
                        self._run_group_cached(fr, group, results)
                    else:
                        self._run_group_uncached(fr, group, results)
            # uncached execution never consults the cache: stamp None even
            # if a cache happens to exist on the shared fragmentation
            if self.cache_mode != "amortized":
                stamp = None
            else:
                c = fr.rvset_cache
                stamp = None if c is None else c.version
            for r in results:
                r.cache_version = stamp
                r.status = Status.DONE
        self.stats.queries += len(queries)
        self.stats.batches += 1
        return results  # type: ignore[return-value]

    # convenience single-query sugar (examples / interactive use)
    def reach(self, s: int, t: int) -> bool:
        return self.run(Reach(int(s), int(t)))[0].answer

    def dist(self, s: int, t: int,
             bound: Optional[int] = None) -> QueryResult:
        return self.run(Dist(int(s), int(t), bound=bound))[0]

    def rpq(self, s: int, t: int, regex: Optional[str] = None,
            automaton: Optional[QueryAutomaton] = None) -> bool:
        return self.run(Rpq(int(s), int(t), regex=regex,
                            automaton=automaton))[0].answer

    # -- internals ---------------------------------------------------------

    def _reach_in_dist(self, fr: Fragmentation) -> bool:
        """Whether a batch's reach reads join its dist group: on the cached
        vmap path once ``fr``'s tropical closure is built, so the dist
        program runs anyway and a reach read never triggers that build.
        On shard_map each batch closes its own tropical matrix and reach
        keeps the cheaper Boolean program (DESIGN.md Sec. 5)."""
        c = fr.rvset_cache
        return (self.cache_mode == "amortized" and self.backend == "vmap"
                and c is not None and c.bl_dist is not None)

    def _frag_blocks(self, fr: Fragmentation, group: ExecutionGroup) -> int:
        """Fragment blocks the group's local stage relaxes its rows over
        (``cache.fragment_blocks``); 0 where no lane stage runs: uncached,
        RPQ and shard_map groups."""
        if (self.cache_mode != "amortized" or self.backend != "vmap"
                or group.kind == "rpq"):
            return 0
        return _cache.fragment_blocks(group.padded_size, fr.k)

    def _resolve_automaton(self, q: Rpq) -> QueryAutomaton:
        if q.automaton is not None:
            return q.automaton
        with self._lock:
            qa = self._regex_cache.get(q.regex)
            if qa is None:
                g = self.fr.g
                label_of = (g.label_of if g.label_names is not None
                            else (lambda name: int(name)))
                qa = build_query_automaton(q.regex, label_of)
                self._regex_cache[q.regex] = qa
            return qa

    def _run_group_cached(self, fr: Fragmentation, group: ExecutionGroup,
                          results) -> None:
        """One compiled batched execution for the whole group (padded to
        the group's bucket size; pad answers are discarded).  On the
        shard_map backend every kind routes through its one-collective
        sharded batch engine, so the paper's guarantees survive fusion for
        all three query classes (DESIGN.md Sec. 3.3)."""
        pairs = group.pairs()
        stats = self._group_stats(fr, group)
        ans, degraded = self._execute_group(fr, group.kind, pairs,
                                            group.automaton)
        with tracing.span("repro.session.assemble"):
            if group.kind == "reach":
                for i, q, a, st in zip(group.indices, group.queries, ans,
                                       stats):
                    results[i] = self._reach_result(q, a, st)
            elif group.kind == "dist":
                # exact distances once; each query's bound applies at
                # answer extraction (this is what lets bounded + exact
                # queries fuse), and a reach read is one with no bound
                for i, q, di, st in zip(group.indices, group.queries, ans,
                                        stats):
                    results[i] = (self._reach_result(q, di >= 0, st)
                                  if isinstance(q, Reach)
                                  else self._dist_result(q, int(di), st))
            else:                                   # rpq
                for i, q, a, st in zip(group.indices, group.queries, ans,
                                       stats):
                    results[i] = self._rpq_result(q, group.automaton, a, st)
            if degraded:
                for i in group.indices:
                    results[i].degraded = True
        self.stats.executions += 1
        self.stats.rows_useful += group.n
        self.stats.rows_padded += group.padded_size
        self.stats.frag_blocks += self._frag_blocks(fr, group)
        self.stats.reach_rows += group.n_reach
        if group.kind == "dist":
            self.stats.reach_fused += group.n_reach

    def _execute_group(self, fr: Fragmentation, kind: str, pairs, qa):
        """One batched engine execution; returns ``(answers, degraded)``.

        On the shard_map backend an engine/upload failure **degrades**
        instead of failing the group: the same batch re-runs on the host
        vmap path, which answers from the host rvset cache — kept repaired
        on every delta exactly so it can serve as the fallback source.
        Answers stay exact; callers flag them ``degraded=True``
        (DESIGN.md Sec. 7)."""
        if self.backend == "shard_map":
            from . import distributed
            try:
                if kind == "reach":
                    return distributed.dis_reach_batch_sharded(
                        fr, pairs, mesh=self._mesh,
                        placement=self.placement, chaos=self.chaos), False
                if kind == "dist":
                    return distributed.dis_dist_batch_sharded(
                        fr, pairs, mesh=self._mesh,
                        placement=self.placement, chaos=self.chaos), False
                return distributed.dis_rpq_batch_sharded(
                    fr, pairs, qa, mesh=self._mesh,
                    placement=self.placement, chaos=self.chaos), False
            except Exception:
                self.stats.degraded_groups += 1
                return self._execute_group_vmap(fr, kind, pairs, qa), True
        return self._execute_group_vmap(fr, kind, pairs, qa), False

    def _execute_group_vmap(self, fr: Fragmentation, kind: str, pairs, qa):
        if self.chaos is not None:
            self.chaos.maybe_fail("engine.vmap", pairs=pairs)
        if kind == "reach":
            return _cache.dis_reach_batch(fr, pairs)
        if kind == "dist":
            return _cache.dis_dist_batch(fr, pairs)
        return _cache.dis_rpq_batch(fr, pairs, qa)

    def _run_group_uncached(self, fr: Fragmentation, group: ExecutionGroup,
                            results) -> None:
        """Seed one-shot engine, one evaluation per query (cache='none')."""
        for i, q in zip(group.indices, group.queries):
            if group.kind == "reach":
                results[i] = exec_reach(fr, q.s, q.t,
                                        return_matrix=q.return_matrix)
            elif group.kind == "dist":
                results[i] = exec_dist(fr, q.s, q.t, bound=q.bound)
            else:
                results[i] = exec_rpq(fr, q.s, q.t, group.automaton,
                                      return_matrix=q.return_matrix)
            self.stats.executions += 1

    def _group_stats(self, fr: Fragmentation,
                     group: ExecutionGroup) -> List[QueryStats]:
        """Per-query stats whose SUM over the group is exact: a fused group
        ships ONE collective of ``traffic_bits(kind, states, batch=padded)``
        bits total (the padded batch is what actually rides the wire), so
        the bits are amortized across the group's queries with an integer
        fair split and the single collective round is stamped on the first
        query — summing :class:`QueryStats` over any group then reports
        the group's real wire cost instead of overstating it N-fold."""
        states = 1 if group.automaton is None else group.automaton.n_states
        total = fr.traffic_bits(group.kind, states=states,
                                batch=group.padded_size)
        n = group.n
        return [QueryStats(total * (i + 1) // n - total * i // n,
                           1 if i == 0 else 0, fr.B, states)
                for i in range(n)]

    def _reach_result(self, q: Reach, ans, stats: QueryStats) -> QueryResult:
        if q.s == q.t:
            return QueryResult(True, 0, stats)
        return QueryResult(bool(ans), None, stats)

    def _dist_result(self, q: Dist, d: int, stats: QueryStats) -> QueryResult:
        if q.s == q.t:
            ok = q.bound is None or 0 <= q.bound
            return QueryResult(ok, 0, stats)
        dist: Optional[int] = None if d < 0 else d
        reachable = dist is not None
        answer = (reachable if q.bound is None
                  else (reachable and dist <= q.bound))
        # match the seed path: a failed bounded query reports no distance
        if q.bound is not None and not answer:
            dist = None
        return QueryResult(answer, dist, stats)

    def _rpq_result(self, q: Rpq, qa: QueryAutomaton, ans,
                    stats: QueryStats) -> QueryResult:
        if q.s == q.t:
            return QueryResult(bool(qa.nullable), 0, stats)
        return QueryResult(bool(ans), None, stats)


# ---------------------------------------------------------------------------
# per-fragmentation default sessions (what the core.api shims delegate to)
# ---------------------------------------------------------------------------

def default_session(fr: Fragmentation,
                    cache: str = "amortized") -> QuerySession:
    """Memoized vmap-backend session attached to ``fr`` (one per cache
    mode).  Cache state lives on the fragmentation itself, so default
    sessions and explicitly connected ones always share it."""
    key = "_default_session_" + cache
    sess = fr.__dict__.get(key)
    if sess is None:
        sess = QuerySession(fr, backend="vmap", cache=cache)
        fr.__dict__[key] = sess
    return sess


# ---------------------------------------------------------------------------
# seed one-shot engine (paper Figs. 3-7): full localEval + evalDG per query
# ---------------------------------------------------------------------------
#
# Answer extraction (coordinator side):
#   * source row  = reserved row B-2 (s), in automaton state u_s for RPQs;
#   * target cols = reserved col B-1 (t arrivals internal to t's fragment)
#     plus the alias col b_index[t] when t itself is a boundary in-node
#     (arrivals via a cross edge landing exactly on t).

def _as_jnp(fr: Fragmentation):
    # jnp.array (copy=True), not asarray: the host buffers are mutated in
    # place by apply_delta, and on CPU asarray may alias them (PR 7).
    return {k: jnp.array(v) for k, v in fr.arrays.items()}


def _tgt_cols(fr: Fragmentation, t: int) -> jnp.ndarray:
    B = fr.B
    cols = np.zeros(B, dtype=bool)
    cols[fr.T_COL] = True
    bt = fr.b_index[t]
    if bt >= 0:
        cols[bt] = True
    return jnp.asarray(cols)


def _src_rows(fr: Fragmentation) -> jnp.ndarray:
    rows = np.zeros(fr.B, dtype=bool)
    rows[fr.S_ROW] = True
    return jnp.asarray(rows)


def exec_reach(fr: Fragmentation, s: int, t: int,
               return_matrix: bool = False) -> QueryResult:
    """disReach (paper Fig. 3): vmapped localEval + one assemble + evalDG."""
    if s == t:
        return QueryResult(True, 0, QueryStats(0, 0, fr.B, 1))
    arrs = _as_jnp(fr)
    qs = query_slots(fr, s, t)
    local = jax.vmap(
        lambda es, ed, sl, sr, tl, sloc, tloc: engine.local_eval_reach(
            es, ed, sl, sr, tl, sloc, tloc, n_max=fr.n_max, B=fr.B))
    rlocs = local(arrs["esrc"], arrs["edst"], arrs["src_local"],
                  arrs["src_row"], arrs["tgt_local"],
                  jnp.asarray(qs["s_local"]), jnp.asarray(qs["t_local"]))
    D = jnp.any(rlocs, axis=0)                 # assemble (the one collective)
    ans = engine.evaldg_reach(D, _src_rows(fr), _tgt_cols(fr, t))
    stats = QueryStats(payload_bits=fr.traffic_bits("reach"),
                       collective_rounds=1, boundary=fr.B, states=1)
    return QueryResult(bool(ans), None, stats,
                       np.asarray(D) if return_matrix else None)


def exec_dist(fr: Fragmentation, s: int, t: int,
              bound: Optional[int] = None) -> QueryResult:
    """disDist (paper Sec. 4): bounded reachability q_br(s, t, l); with
    bound=None returns exact dist(s, t) (INF -> unreachable -> None)."""
    if s == t:
        ok = bound is None or 0 <= bound
        return QueryResult(ok, 0, QueryStats(0, 0, fr.B, 1))
    cap = jnp.int32(bound) if bound is not None else INF
    arrs = _as_jnp(fr)
    qs = query_slots(fr, s, t)
    local = jax.vmap(
        lambda es, ed, sl, sr, tl, sloc, tloc: engine.local_eval_dist(
            es, ed, sl, sr, tl, sloc, tloc, cap, n_max=fr.n_max, B=fr.B))
    wlocs = local(arrs["esrc"], arrs["edst"], arrs["src_local"],
                  arrs["src_row"], arrs["tgt_local"],
                  jnp.asarray(qs["s_local"]), jnp.asarray(qs["t_local"]))
    W = jnp.min(wlocs, axis=0)
    d = engine.evaldg_dist(W, _src_rows(fr), _tgt_cols(fr, t))
    d = int(d)
    reachable = d < int(INF)
    answer = reachable if bound is None else (reachable and d <= bound)
    stats = QueryStats(payload_bits=fr.traffic_bits("dist"),
                       collective_rounds=1, boundary=fr.B, states=1)
    # a failed bounded query reports no distance: with the propagation
    # capped at the bound, d is not the true distance past it (local
    # segments longer than the cap were pruned), so don't surface it
    return QueryResult(answer, d if (reachable and answer) else None, stats)


def exec_rpq(fr: Fragmentation, s: int, t: int, qa: QueryAutomaton,
             return_matrix: bool = False) -> QueryResult:
    """disRPQ (paper Sec. 5): product-automaton localEval_r + evalDG_r."""
    if s == t:
        return QueryResult(bool(qa.nullable), 0,
                           QueryStats(0, 0, fr.B, qa.n_states))
    Q = qa.n_states
    arrs = _as_jnp(fr)
    qs = query_slots(fr, s, t)
    q_labels = jnp.asarray(qa.state_labels)
    q_trans = jnp.asarray(qa.trans)
    local = jax.vmap(
        lambda es, ed, sl, sr, tl, lab, gid, sloc, tloc:
        engine.local_eval_regular(es, ed, sl, sr, tl, lab, gid,
                                  q_labels, q_trans, sloc, tloc,
                                  jnp.int32(s), jnp.int32(t),
                                  n_max=fr.n_max, B=fr.B))
    rlocs = local(arrs["esrc"], arrs["edst"], arrs["src_local"],
                  arrs["src_row"], arrs["tgt_local"], arrs["labels"],
                  arrs["gids"],
                  jnp.asarray(qs["s_local"]), jnp.asarray(qs["t_local"]))
    D = jnp.any(rlocs, axis=0)                  # [(B*Q), (B*Q)]

    src_rows = np.zeros(fr.B * Q, dtype=bool)
    src_rows[fr.S_ROW * Q + qa.start] = True
    tgt_cols = np.zeros(fr.B * Q, dtype=bool)
    tgt_cols[fr.T_COL * Q + qa.final] = True
    bt = fr.b_index[t]
    if bt >= 0:
        tgt_cols[bt * Q + qa.final] = True
    ans = engine.evaldg_reach(D, jnp.asarray(src_rows), jnp.asarray(tgt_cols))
    stats = QueryStats(payload_bits=fr.traffic_bits("rpq", states=Q),
                       collective_rounds=1, boundary=fr.B, states=Q)
    return QueryResult(bool(ans), None, stats,
                       np.asarray(D) if return_matrix else None)
