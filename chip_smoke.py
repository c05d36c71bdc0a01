"""Chip smoke run: the served query path end to end on a TPU.

    python chip_smoke.py              # one chip (vmap backend)
    python chip_smoke.py --chips 4    # a four-chip mesh (shard_map backend)

Builds one graph from ``--seed`` with the locality the paper assumes:
``K`` Erdos-Renyi blocks of ``BLOCK`` nodes and ``DEGREE * BLOCK`` edges
each, joined by ``N_CROSS`` uniform edges, fragmented along the blocks.
The graph has a quarter of a million nodes and a million edges while its
boundary ``V_f`` (the in-nodes of crossing edges) stays at a few
thousand.

One chip: ``repro.connect`` -> ``warm(with_dist=True)`` -> a
``QueryServer`` serving mixed reach / exact and bounded dist / RPQ
requests with one ``GraphDelta.insert`` mid-stream, once on the default
barrier path and once with ``mvcc=True`` (the repair worker thread).  It
also lowers the closure and combine programs of both semirings and
requires the Pallas kernels (``tpu_custom_call``) in each.

``--chips 4``: only the four-chip phase -- the same graph on a 4-device
mesh (``backend="shard_map"``, ``Placement.balanced``), served through a
``QueryServer`` with one delta through ``apply_delta_sharded``, the
guarantee verifier run on the real mesh, and every device's peak memory.

Every answer is checked against the networkx oracles of
``tests/oracles.py`` on the snapshot its ``cache_version`` names.  The run
fails -- non-zero exit, no ``ok`` line -- when JAX finds no TPU (it never
falls back to the CPU), on any mismatch, and on any sign that the serving
stack absorbed a fault: a query not DONE, a delta not APPLIED, a dead
letter (the server gets one attempt per batch, so a batch that raises
dead-letters), a degraded result or group, a rollback.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import numpy as np  # noqa: E402

import repro  # noqa: E402
from oracles import GraphOracle  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import (GraphDelta, Placement, build_query_automaton,  # noqa: E402
                        fragment_graph)
from repro.graph import Graph, block_partition, csr_from_coo, erdos_renyi  # noqa: E402
from repro.serve import QueryServer, RetryPolicy, Status  # noqa: E402

K = 16                 # fragments, one Erdos-Renyi block each
BLOCK = 16384          # nodes per block (cut from 65,536: see CHANGES.md)
DEGREE = 4             # intra-block edges per node
N_CROSS = 4096         # uniform edges over the whole graph: |V_f| ~ 3.9k
N_LABELS = 8
REGEX = "0*"           # Glushkov automaton with |Q| = 3
RESERVE = 16           # spare boundary / edge / stub slots for the delta
BATCH = 8              # QueryServer batch size: one bucket per kind
REQUESTS = 240         # mixed requests per serving phase on one chip
REQUESTS_4CHIP = 24    # per segment on the mesh (each batch recomputes)
TIMEOUT_S = 900.0
WATCHDOG_S = 1140.0    # the whole run, compilation included: then it fails


def say(name: str, value) -> None:
    print(f"{name}: {json.dumps(value)}", flush=True)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def build_graph(seed: int, k: int = K, block: int = BLOCK,
                degree: int = DEGREE, n_cross: int = N_CROSS):
    """``k`` Erdos-Renyi blocks joined by ``n_cross`` uniform edges, and
    the block partition that cuts only those."""
    blocks = [erdos_renyi(block, degree * block, n_labels=N_LABELS,
                          seed=seed * (k + 1) + b) for b in range(k)]
    cross = erdos_renyi(k * block, n_cross, seed=seed * (k + 1) + k)
    src = np.concatenate([b * block + gb.src for b, gb in enumerate(blocks)]
                         + [cross.src])
    dst = np.concatenate([b * block + gb.dst for b, gb in enumerate(blocks)]
                         + [cross.dst])
    g = Graph(k * block, src, dst,
              np.concatenate([gb.labels for gb in blocks]))
    return g, block_partition(g, k)


def _walk(csr, labels, rng, s: int, steps: int, via_label=None) -> int:
    """End of a random walk of at most ``steps`` edges from ``s``; with
    ``via_label`` every node strictly inside the walk carries that label
    (so the pair answers a ``via_label*`` RPQ true)."""
    indptr, indices = csr
    v = s
    for i in range(steps):
        if i and via_label is not None and labels[v] != via_label:
            break
        succ = indices[indptr[v]:indptr[v + 1]]
        if succ.size == 0:
            break
        v = int(succ[rng.integers(succ.size)])
    return v


def _request(kind: str, s: int, t: int, rng):
    return (kind, s, t, int(rng.integers(2, 16)) if kind == "bounded"
            else None)


def make_requests(g: Graph, n: int, seed: int):
    """``n`` mixed requests ``(kind, s, t, bound)``: reach, exact dist,
    bounded dist and RPQ in turn, half of them between uniform endpoints
    and half between the ends of a short random walk (for RPQs one
    through label-0 nodes), so that answers of every kind occur."""
    rng = np.random.default_rng([seed, 1])
    csr = csr_from_coo(g.n, g.src, g.dst)
    kinds = ("reach", "dist", "bounded", "rpq")
    out = []
    for i in range(n):
        kind = kinds[i % 4]
        s = int(rng.integers(g.n))
        if i % 8 < 4:
            t = int(rng.integers(g.n))
        else:
            t = _walk(csr, g.labels, rng, s, int(rng.integers(1, 7)),
                      via_label=0 if kind == "rpq" else None)
        out.append(_request(kind, s, t, rng))
    return out


def make_delta(g: Graph, part: np.ndarray, seed: int):
    """One insertion that changes answers: a node ``s0`` of fragment 0 with
    no out-edge gains one edge inside its fragment and one crossing edge
    into fragment 1 (activating a spare boundary slot).  Returns the delta
    and requests of every kind from ``s0``, which answer false before the
    delta and mostly true after it."""
    rng = np.random.default_rng([seed, 2])
    out_deg = np.bincount(g.src, minlength=g.n)
    s0 = int(rng.choice(np.nonzero((out_deg == 0) & (part == 0))[0]))
    x = int(rng.choice(np.nonzero((out_deg > 0) & (part == 1))[0]))
    y = int(rng.choice(np.nonzero((out_deg > 0) & (part == 0))[0]))
    delta = GraphDelta.insert([(s0, x), (s0, y)])
    csr = csr_from_coo(g.n, g.src, g.dst)
    targets = [x, y] + [_walk(csr, g.labels, rng, v, int(rng.integers(1, 5)))
                        for v in (x, x, y, y)]
    reqs = [_request(kind, s0, t, rng) for t in targets
            for kind in ("reach", "dist", "bounded", "rpq")]
    return delta, reqs


# ---------------------------------------------------------------------------
# serving and checking
# ---------------------------------------------------------------------------

def _submit(srv: QueryServer, req):
    kind, s, t, bound = req
    if kind == "rpq":
        return srv.submit(s, t, kind="rpq", regex=REGEX)
    return srv.submit(s, t, kind=kind, bound=bound)


def _head_version(srv: QueryServer) -> int:
    if srv.store is not None:
        return srv.store.head().cache_version
    return srv.session.cache_version


def serve(session, before, during, after, delta, *, mvcc: bool = False,
          start: bool = True, batch_size: int = BATCH):
    """Serve the requests ``before``, submit ``delta``, serve ``during``
    while it applies, wait for its commit, then serve ``after``.  Returns
    ``(observed, failures, report)``: ``observed`` holds ``(request,
    snapshot, answer)`` with snapshot "pre" or "post" as named by each
    answer's ``cache_version``."""
    # one attempt: a batch that raises is a fault to report, not to retry
    srv = QueryServer(session.fr, session=session, batch_size=batch_size,
                      mvcc=mvcc, start=start,
                      retry=RetryPolicy(max_attempts=1))
    t0 = time.perf_counter()
    v_pre = _head_version(srv)
    before = [(r, _submit(srv, r)) for r in before]
    upd = srv.submit_delta(delta)
    during = [(r, _submit(srv, r)) for r in during]
    if not start:
        srv.flush()
    failures = []
    try:
        upd.result(timeout=TIMEOUT_S)
    except Exception as exc:    # noqa: BLE001 - reported as a failure
        failures.append(f"delta raised {exc!r}")
    v_post = _head_version(srv)
    say("delta_committed", dict(mvcc=mvcc, wall_s=time.perf_counter() - t0,
                                dead_letters=len(srv.dead_letters)))
    after = [(r, _submit(srv, r)) for r in after]
    srv.close()
    if upd.status != Status.APPLIED:
        failures.append(f"delta resolved {upd.status}, not APPLIED")
    if v_post == v_pre:
        failures.append(f"delta left cache_version at {v_pre}")
    # which snapshots each group of answers may name: the barrier fences
    # the queue at the delta, MVCC reads pin whatever head is current
    allowed = [(before, {v_pre} if not mvcc else {v_pre, v_post}),
               (during, {v_post} if not mvcc else {v_pre, v_post}),
               (after, {v_post})]
    observed = []
    for group, versions in allowed:
        for req, fut in group:
            if fut.status != Status.DONE:
                failures.append(f"{req} resolved {fut.status}: {fut.error!r}")
                continue
            if fut.degraded:
                failures.append(f"{req} was served degraded")
            if fut.cache_version not in versions:
                failures.append(f"{req} answered snapshot "
                                f"{fut.cache_version}, expected {versions}")
                continue
            snap = "pre" if fut.cache_version == v_pre else "post"
            observed.append((req, snap, fut.value))
    if srv.dead_letters:
        failures.append(f"{len(srv.dead_letters)} dead letters")
    stats = session.stats
    if stats.degraded_groups:
        failures.append(f"{stats.degraded_groups} degraded groups")
    if stats.rollbacks:
        failures.append(f"{stats.rollbacks} rollbacks")
    served = {}
    for req, snap, _ in observed:
        key = f"{req[0]}/{snap}"
        served[key] = served.get(key, 0) + 1
    report = dict(mvcc=mvcc, requests=len(before) + len(during) + len(after),
                  served=served, update=getattr(upd.value, "mode", None),
                  dead_letters=len(srv.dead_letters),
                  degraded_groups=stats.degraded_groups,
                  rollbacks=stats.rollbacks, batches=srv.batches_run,
                  snapshots=[v_pre, v_post])
    return observed, failures, report


def oracle_answer(oracle: GraphOracle, qa, req):
    kind, s, t, bound = req
    if kind == "reach":
        return oracle.reach(s, t)
    if kind == "rpq":
        return oracle.rpq(s, t, qa)
    d = oracle.dist(s, t)
    return d if kind == "dist" else (d is not None and d <= bound)


def check_answers(g: Graph, delta: GraphDelta, observed):
    """Compare every observed answer with the oracle on its snapshot: the
    pre-delta graph first, then the same oracle graph with the delta's
    edges inserted.  Returns ``(mismatches, counts)``."""
    qa = build_query_automaton(REGEX, int)
    oracle = GraphOracle(g)
    mismatches, counts = [], {}
    for snap in ("pre", "post"):
        if snap == "post":
            oracle.add_edges(delta.add_src, delta.add_dst)
        memo = {}
        for req, seen_snap, got in observed:
            if seen_snap != snap:
                continue
            if req not in memo:
                memo[req] = oracle_answer(oracle, qa, req)
            want = memo[req]
            key = f"{req[0]}={want is not None and want is not False}"
            counts[key] = counts.get(key, 0) + 1
            if got != want:
                mismatches.append(f"{req} on {snap}: got {got!r}, "
                                  f"oracle {want!r}")
    return mismatches, counts


# ---------------------------------------------------------------------------
# device-side reporting
# ---------------------------------------------------------------------------

def cache_bytes(fr) -> dict:
    c = fr.rvset_cache
    out = {f"arrays.{k}": int(v.nbytes) for k, v in c.arrays.items()}
    for name in ("bl_frontier", "closure", "bl_dist", "dist_closure"):
        v = getattr(c, name)
        if v is not None:
            out[name] = int(v.nbytes)
    for i, v in enumerate(c.rpq_closures.values()):
        out[f"rpq_closure[{i}]"] = int(v.nbytes)
    return out


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def kernel_programs(nb: int, side: int) -> dict:
    """Lower the cache's closure programs (``bes.bool_closure`` at the
    reach and RPQ sides, ``bes.tropical_closure``) and the per-batch
    combines of both semirings at this graph's widths; True where the
    lowered program calls a Pallas kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    from repro.core import bes, cache

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    b, i, n = jnp.bool_, jnp.int32, BATCH
    progs = {
        "bool_closure": (bes.bool_closure, [sds((nb, nb), b)]),
        "bool_closure_rpq": (bes.bool_closure, [sds((side, side), b)]),
        "tropical_closure": (bes.tropical_closure, [sds((nb, nb), i)]),
        "bool_combine": (cache.combine_bool, [
            sds((n,), b), sds((n, nb), b), sds((n, nb), b),
            sds((nb, nb), b)]),
        "tropical_combine": (cache.combine_dist, [
            sds((n,), i), sds((n, nb), i), sds((n, nb), i),
            sds((nb, nb), i)]),
    }
    return {name: "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()
            for name, (fn, args) in progs.items()}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _fragment(seed: int, **graph):
    t0 = time.perf_counter()
    g, part = build_graph(seed, **graph)
    fr = fragment_graph(g, part, int(part.max()) + 1,
                        reserve_boundary=RESERVE, reserve_edges=RESERVE,
                        reserve_stubs=RESERVE)
    say("graph", dict(n=g.n, m=g.m, k=fr.k, nb=fr.n_boundary,
                      nb_active=fr.nb_active, B=fr.B, n_max=fr.n_max,
                      e_max=fr.e_max, s_max=fr.s_max,
                      host_build_wall_s=time.perf_counter() - t0))
    return g, part, fr


def barrier_and_mvcc(session, g: Graph, part, n_requests: int, seed: int,
                     devices=()):
    """The one-chip serving phase on a warmed session: the same requests
    and delta served with ``mvcc=True``, then on the barrier path, then
    every answer checked.  Returns ``(failures, observed)``."""
    reqs = make_requests(g, n_requests, seed)
    delta, cross = make_delta(g, part, seed)
    failures, observed = [], []
    # MVCC first: its delta commits to a copy-on-write clone, so the
    # session's own fragmentation is still pre-delta for the barrier run
    for mvcc in (True, False):
        t0 = time.perf_counter()
        obs, fails, report = serve(session, cross + reqs[:n_requests // 2],
                                   reqs[n_requests // 2:], cross, delta,
                                   mvcc=mvcc)
        report["wall_s"] = time.perf_counter() - t0
        if devices:
            report["peak_bytes_in_use"] = peak_bytes(devices)[0]
        say("serve_mvcc" if mvcc else "serve_barrier", report)
        observed += obs
        failures += fails
        gc.collect()
    t0 = time.perf_counter()
    mismatches, counts = check_answers(g, delta, observed)
    say("oracle", dict(checked=len(observed), mismatches=len(mismatches),
                       answers=counts,
                       host_wall_s=time.perf_counter() - t0))
    return failures + mismatches, observed


def one_chip(seed: int, devices) -> list:
    g, part, fr = _fragment(seed)
    session = repro.connect(fr, backend="vmap")
    t0 = time.perf_counter()
    session.warm(with_dist=True)
    fr.rvset_cache.dist_closure.block_until_ready()
    say("warm", dict(wall_s=time.perf_counter() - t0,
                     peak_bytes_in_use=peak_bytes(devices)[0]))
    say("cache_bytes", cache_bytes(fr))
    qa = build_query_automaton(REGEX, int)
    custom = kernel_programs(fr.n_boundary, fr.n_boundary * qa.n_states)
    say("tpu_custom_call", custom)
    failures = [f"no Pallas kernel in the {name} program"
                for name, ok in custom.items() if not ok]
    fails, _ = barrier_and_mvcc(session, g, part, REQUESTS, seed, devices)
    say("cache_bytes_after", cache_bytes(fr))
    return failures + fails


def four_chips(seed: int, devices, **graph) -> list:
    """The mesh phase; ``graph`` overrides :func:`build_graph`'s sizes
    (the CPU rehearsal runs it small on fake devices)."""
    from repro.analysis.hlo_check import verify_session
    from repro.core.distributed import fragment_mesh

    g, part, fr = _fragment(seed, **graph)
    mesh = fragment_mesh(4, devices=devices[:4])
    placement = Placement.balanced(fr, 4)
    session = repro.connect(fr, backend="shard_map", mesh=mesh,
                            placement=placement)
    say("placement", dict(backend=session.backend, d=placement.d,
                          fpd=placement.fpd,
                          device_of=list(placement.device_of)))
    t0 = time.perf_counter()
    # the reach cache only: a tropical cache sends deltas down the host
    # repair path instead of apply_delta_sharded
    session.warm()
    say("warm", dict(wall_s=time.perf_counter() - t0))
    # three segments of 6 requests per kind, one batch each, so every
    # segment compiles into the same three sharded programs
    reqs = make_requests(g, 2 * REQUESTS_4CHIP, seed)
    delta, cross = make_delta(g, part, seed)
    t0 = time.perf_counter()
    observed, failures, report = serve(
        session, reqs[:REQUESTS_4CHIP], reqs[REQUESTS_4CHIP:], cross, delta,
        start=False, batch_size=REQUESTS_4CHIP)
    report["wall_s"] = time.perf_counter() - t0
    say("serve_shard_map", report)
    if report["update"] != "repair_sharded":
        failures.append(f"delta took the {report['update']!r} path, not "
                        "apply_delta_sharded")
    violations = verify_session(session)
    say("verify_session", [str(v) for v in violations])
    failures += [f"guarantee violated: {v}" for v in violations]
    say("peak_bytes_in_use", peak_bytes(devices[:4]))
    mismatches, counts = check_answers(g, delta, observed)
    say("oracle", dict(checked=len(observed), mismatches=len(mismatches),
                       answers=counts))
    return failures + mismatches


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip mesh phase")
    args = p.parse_args(argv)
    # a hung phase fails with the stacks that show where, instead of
    # running into the caller's time limit with nothing to show
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        return _run(args)
    finally:
        faulthandler.cancel_dump_traceback_later()


def _run(args) -> int:
    use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"{jax.default_backend()!r}); not falling back to it",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    run = four_chips if args.chips == 4 else one_chip
    failures = run(args.seed, devices)
    if failures:
        for f in failures[:50]:
            print(f"FAIL {f}", file=sys.stderr)
        print(f"chip_smoke: {len(failures)} failure(s)", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
