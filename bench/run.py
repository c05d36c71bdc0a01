"""Run one benchmark cell once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a configuration, whose graph is made from
``--seed``, and a traffic mix.  The run builds the served query path
(``repro.connect`` -> ``QueryServer``), warms up every program the window
can run, keeps the traffic's closed loop going for ``--seconds`` (the
window closes on the first batch to complete after that), waits for the
answers, compares them with the plain reference, and prints as the last
line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window), ``device``,
``breakdown`` (traced runs) and ``checks``, each number compared beside its
limit; the checks are also the last lines of standard error.

It refuses, with a non-zero exit and no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script, Python puts bench/ itself first on the path; its module
# names are meant as bench.<name> only
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# a hung run ends with every thread's stack rather than running into the
# caller's limit; a cell's first run in a checkout compiles for minutes
WATCHDOG_S = 1150.0


_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    pass


def _check_device(chips: int):
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX found no TPU (default backend "
                     f"{jax.default_backend()!r}); the benchmark does not "
                     "run elsewhere")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_tpu: bool = True,
             override=None, controls=(), t_start: float = None):
    """One run of cell ``name``; returns ``(result, info)``: the result
    object and what the run prints on its ``info`` line.  ``override``
    (tests) may edit the configuration and traffic dicts in place before
    anything is built; each of ``controls`` also compares the control
    answers of that mode (``bench/check.py``) into ``info``."""
    from bench import check, drive, spec as spec_mod, xplane as trace_mod
    from bench import workload as wl
    import numpy as np

    t_start = T_START if t_start is None else t_start
    spec = spec_mod.Spec(root)
    cell = spec.cell(name)
    cfg = spec.config(cell["config"])
    tr = spec.traffic(cell["traffic"])
    if override is not None:
        override(cfg, tr)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    # every program, however quick to compile, comes from the cache in a
    # run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_tpu:
        devices = _check_device(cell["chips"])
    else:
        devices = jax.devices()
    compiles = []
    # a backend compile, or a program loaded from the persistent cache:
    # inside the window, either means the warm-up missed a shape
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((time.monotonic(), event))
        if event in _COMPILE_EVENTS else None)

    g = wl.build_graph(cfg, seed)
    system = drive.build(cfg, g)
    warm = drive.warm_up(system, cfg, tr, g, seed)
    rec = drive.Record()
    drive.instrument(system.session, system.server, rec)
    maker = wl.ReadMaker(g, tr, np.random.default_rng([seed, 1]))
    srv = system.server
    base_version = (srv.store.head().cache_version if srv.store is not None
                    else system.session.cache_version)
    stats0 = dict(vars(system.session.stats))
    setup_s = time.monotonic() - t_start

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    try:
        t0, t1, sent = drive.run_closed(system, maker, tr["outstanding"],
                                        seconds, tr["regex"], rec)
    finally:
        if trace:
            jax.profiler.stop_trace()
    missing = drive.wait_all(sent, t1 + tr["result_wait_s"])
    peak = drive.peak_bytes(devices[:cell["chips"]])
    stats = {k: v - stats0.get(k, 0) for k, v in
             vars(system.session.stats).items()}
    srv.close()
    del system, srv
    gc.collect()

    checks = check.compare(sent, g, tr, seed, base_version, t1)
    control_checks = {mode: check.compare(sent, g, tr, seed, base_version,
                                          t1, control=mode)
                      for mode in controls}
    in_window = [c for c in compiles if t0 <= c[0] <= t1]
    red = None
    if trace:
        red = trace_mod.reduce_trace(trace_mod.find_xplane(tdir),
                                     cell["chips"])
        shutil.rmtree(tdir, ignore_errors=True)
    run = dict(sent=sent, t0=t0, t1=t1, setup_s=setup_s, record=rec,
               trace=red, cfg=cfg, tr=tr, cell=cell, stats=stats,
               missing=missing)
    metrics = spec_mod.read_metrics(spec, name, trace, run)
    attempted, failed = _outcomes(sent, t1)
    # every number compared is a count of faults: its limit is 0
    compared = {k: v for k, v in checks.items() if k != "checked"}
    correct = all(v <= 0 for v in compared.values())
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = trace_mod.breakdown(red)
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in compared.items()}
    closed = [c for t, c, _ in rec.batches if t0 <= t <= t1]
    info = dict(warm=warm, window_s=t1 - t0,
                window_batches=len(closed), window_batch_reads=sum(closed),
                compiles_in_window=len(in_window),
                compile_events_in_window=sorted({e for _, e in in_window}),
                missing=missing, session_stats=stats,
                checked=checks["checked"], control_checks=control_checks,
                reads_sent=len(sent))
    return result, info


def _outcomes(sent, t1: float):
    """The window's reads (those answered within it) and how many of them
    failed: not ``done`` (dead-lettered, say) or served degraded."""
    attempted = failed = 0
    for s in sent:
        if not (s.fut.done() and s.fut.resolved_at <= t1):
            continue
        attempted += 1
        if str(s.fut.status) != "done" or getattr(s.fut, "degraded", False):
            failed += 1
    return attempted, failed


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    try:
        result, info = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
    print("info " + json.dumps(info), file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
