"""CLI: ``python -m repro.analysis --all`` — run every static pass and
exit non-zero on violations.  See DESIGN.md Sec. 10.

The HLO pass lowers the real sharded programs on 8 fake CPU devices.
Whether this process can host that pass is decided from its environment
alone, before any JAX backend starts: asking JAX for its devices would
start the default backend, and on a TPU host that takes the chip, which a
child could then not share.  Unless the process was started on the CPU
platform with the fake-device flag, the CLI re-execs itself once with
``JAX_PLATFORMS=cpu`` and the flag in the child's environment.
"""
import argparse
import json
import os
import subprocess
import sys

_DEVICE_FLAG = "--xla_force_host_platform_device_count=8"


def _ensure_devices(argv, min_devices=8):
    """Return None if this process was started for the HLO pass (CPU
    platform, fake-device flag set), else the exit code of a child
    started that way."""
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and _DEVICE_FLAG in os.environ.get("XLA_FLAGS", "")):
        import jax
        if jax.local_device_count() >= min_devices:
            return None
        print(f"error: {jax.local_device_count()} device(s) visible even "
              f"under {_DEVICE_FLAG}", file=sys.stderr)
        return 2
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _DEVICE_FLAG).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.call(
        [sys.executable, "-m", "repro.analysis", *argv], env=env)


def _hlo_section(batch):
    import repro
    from repro.core import GraphDelta, fragment_graph
    from repro.core.versions import VersionedCacheStore
    from repro.graph import erdos_renyi, random_partition

    from .hlo_check import verify_store

    reserve = dict(reserve_boundary=16, reserve_edges=32, reserve_stubs=16)
    configs = [
        # exact fit: k = d = 8, one fragment per device
        ("k8d8", erdos_renyi(48, 140, n_labels=4, seed=5), 8),
        # packed: k = 32 fragments on 8 devices, fpd = 4
        ("k32d8", erdos_renyi(96, 300, n_labels=4, seed=9), 32),
    ]
    violations, covered = [], []
    for name, g, k in configs:
        fr = fragment_graph(g, random_partition(g, k, 1), k, **reserve)
        sess = repro.connect(fr, backend="shard_map")
        store = VersionedCacheStore(sess, capacity=4)
        store.commit_delta(GraphDelta.insert([(0, 1)]))
        live = list(store.live())
        assert len(live) >= 2, f"{name}: expected >= 2 live versions"
        for v in verify_store(store, batch=batch):
            v.where = f"{name}:{v.where}"
            violations.append(v)
        covered.append(f"{name}: {len(live)} versions x 3 kinds "
                       f"(d={sess.placement.d}, fpd={sess.placement.fpd})")
    return violations, {"covered": covered}


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static guarantee verifier + concurrency lint")
    p.add_argument("--all", action="store_true",
                   help="run every pass (default if none selected)")
    p.add_argument("--hlo", action="store_true",
                   help="lower + verify the sharded programs (HLO001-004)")
    p.add_argument("--lint", action="store_true",
                   help="AST lint over src/repro (RPR001-005)")
    p.add_argument("--locks", action="store_true",
                   help="static lock-order check (LCK001-003)")
    p.add_argument("--root", default=os.getcwd(),
                   help="repo root (default: cwd)")
    p.add_argument("--batch", type=int, default=2,
                   help="fused batch size for the HLO pass")
    p.add_argument("--out", default=None, help="write the JSON report here")
    argv = sys.argv[1:] if argv is None else list(argv)
    args = p.parse_args(argv)
    if args.all or not (args.hlo or args.lint or args.locks):
        args.hlo = args.lint = args.locks = True

    if args.hlo:
        rc = _ensure_devices(argv)
        if rc is not None:
            return rc

    from .report import dump_report, make_report

    sections, extra = {}, {}
    if args.hlo:
        sections["hlo"], extra["hlo"] = _hlo_section(args.batch)
    if args.lint:
        from .lint import lint_paths
        src = os.path.join(args.root, "src", "repro")
        sections["lint"] = lint_paths([src if os.path.isdir(src)
                                       else args.root])
    if args.locks:
        from .locks import LOCK_ORDER, check_lock_order
        vs, edges = check_lock_order(args.root)
        sections["locks"] = vs
        extra["locks"] = {"order": list(LOCK_ORDER),
                          "edges": sorted(f"{a} -> {b}" for a, b in edges)}

    report = make_report(sections, extra=extra)
    if args.out:
        dump_report(report, args.out)
    json.dump(report, sys.stdout, indent=2)
    print()
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
