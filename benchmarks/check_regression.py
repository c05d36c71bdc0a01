"""Benchmark-regression gate (CI step, see .github/workflows/ci.yml).

Compares the fresh fast-mode results (``BENCH_*.fast.json``, written by
``python -m benchmarks.run --fast``) against the committed full-run
baselines (``BENCH_*.json``), and sanity-checks the committed baselines
themselves, so a perf regression fails the build instead of silently
shipping in an artifact:

* ``warm_batched_per_query_us`` (fast run) must not exceed 2x the committed
  full-run value — the fast config is ~4x smaller, so honoring this bound
  is easy unless the warm path actually regressed;
* ``payload_shrink_factor`` (fast run) must stay >= 8 — the bitpacked
  collective must keep its 8x advantage over uint8 shipping;
* committed ``BENCH_pr3.json`` must show incremental repair beating a full
  cache rebuild by >= 5x median at the Table-2 config, and the fast run
  must clear a small-graph floor (overheads dominate tiny matrices);
* mixed-kind session batches (``BENCH_pr4``): the fast-run warm
  per-query cost must not exceed 2x the committed full-run value (the fast
  config is ~3x smaller), and fusing a mixed reach+dist+RPQ batch must
  beat the per-kind serving loop (committed >= 3x, fast >= a small-graph
  floor — the RPQ group is what the per-kind loop cannot batch);
* sharded mixed batches (``BENCH_pr5``): both runs must report
  ``answers_match`` (shard_map == vmap answers on the mixed workload) and
  ``payload_bits_ok`` (summed per-group QueryStats == the wire size of
  each group's single collective), and the fast-run shard_map per-query
  cost must not exceed 3x the committed value (fake-device collectives on
  one CPU are noisier than the vmap path, hence the looser factor);
* k >> d scale-out (``BENCH_pr6``): every packing factor row (k fragments
  on 8 devices) in both runs must report ``answers_match`` and
  ``payload_bits_ok`` — packing must change neither answers nor the wire
  — and the fast run's densest-packing per-query cost must not exceed 3x
  the committed value;
* chaos serving (``BENCH_pr7``): both runs must report ``answers_ok``
  (every answered result exact against the delta-replay oracle) and a
  request ``success_rate`` >= 0.99 under the seeded 1% fault schedule,
  and the fast run's steady-state p95 per-query latency must not exceed
  3x the committed value;
* async continuous batching (``BENCH_pr8``): both runs must report
  ``answers_ok`` (every mode of the equal-work comparison plus the
  open-loop phase oracle-exact); the committed run's async engine must
  at least match the synchronous drain pattern's throughput at equal
  work (``throughput_ratio`` >= 1.0; the fast run gets a noise
  allowance), and the fast run's open-loop p99 latency must stay within
  3x the committed baseline (with a small-run absolute floor);
* MVCC snapshot serving (``BENCH_pr9``): both runs must report
  ``answers_ok`` (every read verified against the graph snapshot named
  by its stamped ``cache_version``); the committed
  run's worst-mix barrier/mvcc read-p95 ratio must show MVCC retiring
  the write stall by >= 2x (the fast run gets a noise floor).

Exits non-zero with a FAIL line per violated bound.
"""
from __future__ import annotations

import json
import sys

WARM_REGRESSION_FACTOR = 2.0
MIN_PAYLOAD_SHRINK = 8.0
MIN_REPAIR_SPEEDUP_FULL = 5.0
MIN_REPAIR_SPEEDUP_FAST = 2.0
MIXED_REGRESSION_FACTOR = 2.0
MIN_FUSED_SPEEDUP_FULL = 3.0
MIN_FUSED_SPEEDUP_FAST = 1.3
SHARDED_REGRESSION_FACTOR = 3.0
MIN_CHAOS_SUCCESS_RATE = 0.99
CHAOS_P95_REGRESSION_FACTOR = 3.0
MIN_ASYNC_THROUGHPUT_RATIO_FULL = 1.0
MIN_ASYNC_THROUGHPUT_RATIO_FAST = 0.7
ASYNC_P99_REGRESSION_FACTOR = 3.0
ASYNC_P99_FLOOR_MS = 50.0
MIN_MVCC_P95_RATIO_FULL = 2.0
MIN_MVCC_P95_RATIO_FAST = 1.2


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else "."
    failures = []

    def check(name, ok, detail):
        status = "PASS" if ok else "FAIL"
        print(f"{status} {name}: {detail}")
        if not ok:
            failures.append(name)

    base2 = _load(f"{root}/BENCH_pr2.json")
    fast2 = _load(f"{root}/BENCH_pr2.fast.json")
    warm_base = base2["warm_batched_per_query_us"]
    warm_fast = fast2["warm_batched_per_query_us"]
    check(
        "warm_batched_per_query_us",
        warm_fast <= WARM_REGRESSION_FACTOR * warm_base,
        f"fast {warm_fast:.1f}us vs committed {warm_base:.1f}us "
        f"(limit {WARM_REGRESSION_FACTOR}x)",
    )
    shrink = fast2["payload_shrink_factor"]
    check(
        "payload_shrink_factor",
        shrink >= MIN_PAYLOAD_SHRINK,
        f"fast {shrink:.2f} (floor {MIN_PAYLOAD_SHRINK})",
    )

    base3 = _load(f"{root}/BENCH_pr3.json")
    fast3 = _load(f"{root}/BENCH_pr3.fast.json")
    sp_full = base3["repair_speedup_median"]
    check(
        "repair_speedup_median (committed, Table-2 cfg)",
        sp_full >= MIN_REPAIR_SPEEDUP_FULL,
        f"committed {sp_full:.2f}x (floor {MIN_REPAIR_SPEEDUP_FULL}x)",
    )
    sp_fast = fast3["repair_speedup_median"]
    check(
        "repair_speedup_median (fast run)",
        sp_fast >= MIN_REPAIR_SPEEDUP_FAST,
        f"fast {sp_fast:.2f}x (floor {MIN_REPAIR_SPEEDUP_FAST}x)",
    )

    base4 = _load(f"{root}/BENCH_pr4.json")
    fast4 = _load(f"{root}/BENCH_pr4.fast.json")
    mixed_base = base4["mixed_per_query_us"]
    mixed_fast = fast4["mixed_per_query_us"]
    check(
        "mixed_per_query_us",
        mixed_fast <= MIXED_REGRESSION_FACTOR * mixed_base,
        f"fast {mixed_fast:.1f}us vs committed {mixed_base:.1f}us "
        f"(limit {MIXED_REGRESSION_FACTOR}x)",
    )
    fs_full = base4["fused_speedup"]
    check(
        "fused_speedup (committed)",
        fs_full >= MIN_FUSED_SPEEDUP_FULL,
        f"committed {fs_full:.2f}x (floor {MIN_FUSED_SPEEDUP_FULL}x)",
    )
    fs_fast = fast4["fused_speedup"]
    check(
        "fused_speedup (fast run)",
        fs_fast >= MIN_FUSED_SPEEDUP_FAST,
        f"fast {fs_fast:.2f}x (floor {MIN_FUSED_SPEEDUP_FAST}x)",
    )

    base5 = _load(f"{root}/BENCH_pr5.json")
    fast5 = _load(f"{root}/BENCH_pr5.fast.json")
    for tag, rep in (("committed", base5), ("fast", fast5)):
        check(
            f"sharded answers_match ({tag})",
            rep["answers_match"],
            "shard_map answers == vmap answers on the mixed batch",
        )
        check(
            f"sharded payload_bits_ok ({tag})",
            rep["payload_bits_ok"],
            "summed group QueryStats == one-collective wire size",
        )
    sh_base = base5["shard_map_per_query_us"]
    sh_fast = fast5["shard_map_per_query_us"]
    check(
        "shard_map_per_query_us",
        sh_fast <= SHARDED_REGRESSION_FACTOR * sh_base,
        f"fast {sh_fast:.1f}us vs committed {sh_base:.1f}us "
        f"(limit {SHARDED_REGRESSION_FACTOR}x)",
    )

    base6 = _load(f"{root}/BENCH_pr6.json")
    fast6 = _load(f"{root}/BENCH_pr6.fast.json")
    for tag, rep in (("committed", base6), ("fast", fast6)):
        for row in rep["rows"]:
            label = f"k={row['k']} fpd={row['fragments_per_device']}"
            check(
                f"scaleout answers_match ({tag}, {label})",
                row["answers_match"],
                "packed shard_map answers == vmap answers",
            )
            check(
                f"scaleout payload_bits_ok ({tag}, {label})",
                row["payload_bits_ok"],
                "summed group QueryStats == one-collective wire size",
            )
    dense_base = max(base6["rows"], key=lambda r: r["fragments_per_device"])
    dense_fast = max(fast6["rows"], key=lambda r: r["fragments_per_device"])
    check(
        "scaleout per_query_us (densest packing)",
        dense_fast["per_query_us"]
        <= SHARDED_REGRESSION_FACTOR * dense_base["per_query_us"],
        f"fast {dense_fast['per_query_us']:.1f}us vs committed "
        f"{dense_base['per_query_us']:.1f}us "
        f"(limit {SHARDED_REGRESSION_FACTOR}x)",
    )

    base7 = _load(f"{root}/BENCH_pr7.json")
    fast7 = _load(f"{root}/BENCH_pr7.fast.json")
    for tag, rep in (("committed", base7), ("fast", fast7)):
        check(
            f"chaos answers_ok ({tag})",
            rep["answers_ok"],
            "answered results exact against the delta-replay oracle",
        )
        rate = rep["success_rate"]
        check(
            f"chaos success_rate ({tag})",
            rate >= MIN_CHAOS_SUCCESS_RATE,
            f"{rate:.3f} (floor {MIN_CHAOS_SUCCESS_RATE})",
        )
    p95_base = base7["p95_per_query_us"]
    p95_fast = fast7["p95_per_query_us"]
    check(
        "chaos p95_per_query_us",
        p95_fast <= CHAOS_P95_REGRESSION_FACTOR * p95_base,
        f"fast {p95_fast:.1f}us vs committed {p95_base:.1f}us "
        f"(limit {CHAOS_P95_REGRESSION_FACTOR}x)",
    )

    base8 = _load(f"{root}/BENCH_pr8.json")
    fast8 = _load(f"{root}/BENCH_pr8.fast.json")
    for tag, rep in (("committed", base8), ("fast", fast8)):
        check(
            f"async answers_ok ({tag})",
            rep["answers_ok"],
            "sync-drain, continuous, and open-loop answers all "
            "oracle-exact",
        )
        check(
            f"async route coverage ({tag})",
            len(rep["open_loop"]["routes"]) >= 2,
            f"open-loop telemetry saw routes "
            f"{sorted(rep['open_loop']['routes'])}",
        )
    ratio_full = base8["throughput_ratio"]
    check(
        "async throughput_ratio (committed)",
        ratio_full >= MIN_ASYNC_THROUGHPUT_RATIO_FULL,
        f"committed async/sync {ratio_full:.2f}x "
        f"(floor {MIN_ASYNC_THROUGHPUT_RATIO_FULL}x)",
    )
    ratio_fast = fast8["throughput_ratio"]
    check(
        "async throughput_ratio (fast run)",
        ratio_fast >= MIN_ASYNC_THROUGHPUT_RATIO_FAST,
        f"fast async/sync {ratio_fast:.2f}x "
        f"(floor {MIN_ASYNC_THROUGHPUT_RATIO_FAST}x)",
    )
    p99_base = base8["open_loop"]["p99_ms"]
    p99_fast = fast8["open_loop"]["p99_ms"]
    p99_limit = max(ASYNC_P99_REGRESSION_FACTOR * p99_base,
                    ASYNC_P99_FLOOR_MS)
    check(
        "async open-loop p99_ms",
        p99_fast <= p99_limit,
        f"fast {p99_fast:.1f}ms vs committed {p99_base:.1f}ms "
        f"(limit {p99_limit:.1f}ms)",
    )

    base9 = _load(f"{root}/BENCH_pr9.json")
    fast9 = _load(f"{root}/BENCH_pr9.fast.json")
    for tag, rep in (("committed", base9), ("fast", fast9)):
        check(
            f"mvcc answers_ok ({tag})",
            rep["answers_ok"],
            "every read exact against the per-snapshot replay oracle "
            "(stamped cache_version -> replayed graph), both modes",
        )
    ratio9_full = base9["read_p95_ratio_min"]
    check(
        "mvcc read_p95_ratio_min (committed)",
        ratio9_full >= MIN_MVCC_P95_RATIO_FULL,
        f"committed barrier/mvcc read-p95 {ratio9_full:.2f}x over all "
        f"mixes (floor {MIN_MVCC_P95_RATIO_FULL}x)",
    )
    ratio9_fast = fast9["read_p95_ratio_min"]
    check(
        "mvcc read_p95_ratio_min (fast run)",
        ratio9_fast >= MIN_MVCC_P95_RATIO_FAST,
        f"fast barrier/mvcc read-p95 {ratio9_fast:.2f}x over all mixes "
        f"(floor {MIN_MVCC_P95_RATIO_FAST}x)",
    )

    if failures:
        print(f"regression gate FAILED: {failures}", file=sys.stderr)
        return 1
    print("regression gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
