"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section headers) and
emits the amortized-cache (BENCH_pr2) and incremental-maintenance
(BENCH_pr3) result files.  ``--fast`` runs scaled-down configs and writes
``BENCH_*.fast.json`` so the committed full-run baselines stay intact —
``benchmarks.check_regression`` compares the two in CI.

Any sub-experiment failure is reported at the end and the process exits
non-zero, so a CI benchmark step cannot pass vacuously.
"""
from __future__ import annotations

import json
import sys
import traceback

from repro.compile_cache import use_compile_cache

from . import paper_experiments as pe
from .exp_async_serve import exp_async_serve
from .exp_mvcc import exp_mvcc


def _emit(section: str, rows):
    for row in rows:
        us = next((v for k, v in row.items() if k.endswith("_us")
                   or k == "us_per_query"), 0.0)
        derived = ";".join(f"{k}={v}" for k, v in row.items()
                           if not (k.endswith("_us") or k == "us_per_query"))
        name = row.get("algo") or section
        print(f"{section}/{name},{us:.1f},{derived}")


def main() -> None:
    use_compile_cache()
    fast = "--fast" in sys.argv
    scale = 0.25 if fast else 1.0
    suffix = ".fast.json" if fast else ".json"
    failures = []

    def section(title, fn):
        print(title)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(title)

    def table2():
        _emit("table2", pe.table2_reachability(n=int(3000 * scale) + 100,
                                               m=int(12000 * scale) + 400))

    def fig11a():
        _emit("fig11a", pe.fig11a_vary_fragments(n=int(4000 * scale) + 100,
                                                 m=int(16000 * scale) + 400))

    def fig11b():
        sizes = (500, 1000, 2000) if fast else (1000, 2000, 4000, 8000)
        _emit("fig11b", pe.fig11b_vary_size(sizes=sizes))

    def exp2():
        _emit("exp2", pe.exp2_bounded(n=int(3000 * scale) + 100,
                                      m=int(12000 * scale) + 400))

    def exp3():
        _emit("exp3", pe.exp3_regular(n=int(800 * scale) + 100,
                                      m=int(3200 * scale) + 400))

    def exp4():
        _emit("exp4", pe.exp4_mapreduce(n=int(800 * scale) + 100,
                                        m=int(3200 * scale) + 400))

    def amortized():
        amort = pe.exp_amortized(n=int(3000 * scale) + 100,
                                 m=int(12000 * scale) + 400,
                                 n_q=16 if fast else 64)
        print(f"amortized/cold,{amort['cold_single_query_us']:.1f},")
        print("amortized/warm_batched,"
              f"{amort['warm_batched_per_query_us']:.1f},"
              f"speedup={amort['speedup']:.1f};"
              f"payload_shrink={amort['payload_shrink_factor']:.2f}")
        out = "BENCH_pr2" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "amortized_rvset_cache",
                       "fast_mode": fast, **amort}, f, indent=2)
        print(f"# wrote {out}")

    def incremental():
        inc = pe.exp_incremental(n=int(3000 * scale) + 100,
                                 m=int(12000 * scale) + 400,
                                 n_q=16 if fast else 64)
        print(f"incremental/repair,{inc['repair_ms_median'] * 1e3:.1f},"
              f"speedup_vs_rebuild={inc['repair_speedup_median']:.1f}")
        print("incremental/full_rebuild,"
              f"{inc['full_rebuild_ms_median'] * 1e3:.1f},")
        print("incremental/warm_query_after_deltas,"
              f"{inc['warm_after_delta_us']:.1f},"
              f"before={inc['warm_before_delta_us']:.1f}")
        out = "BENCH_pr3" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "incremental_cache_maintenance",
                       "fast_mode": fast, **inc}, f, indent=2)
        print(f"# wrote {out}")

    section("# paper Table 2: reachability time/traffic/visits", table2)
    section("# paper Fig 11(a): vary card(F)", fig11a)
    section("# paper Fig 11(b): vary size(F)", fig11b)
    section("# paper Exp-2: bounded reachability", exp2)
    section("# paper Exp-3: regular reachability + query complexity", exp3)
    section("# paper Exp-4: MapReduce", exp4)
    def session_bench():
        res = pe.exp_session(n=int(800 * scale) + 100,
                             m=int(3200 * scale) + 400,
                             n_q=24 if fast else 96)
        print(f"session/mixed_batch,{res['mixed_per_query_us']:.1f},"
              f"fused_speedup={res['fused_speedup']:.2f};"
              f"n_groups={res['n_groups']}")
        print("session/per_kind_loop,"
              f"{res['per_kind_loop_per_query_us']:.1f},")
        out = "BENCH_pr4" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "session_mixed_batches",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    def sharded_mixed():
        res = pe.exp_sharded_mixed(n=int(320 * scale) + 80,
                                   m=int(1280 * scale) + 320,
                                   n_q=24 if fast else 48)
        print("sharded_mixed/shard_map,"
              f"{res['shard_map_per_query_us']:.1f},"
              f"vmap_us={res['vmap_per_query_us']:.1f};"
              f"answers_match={res['answers_match']};"
              f"payload_bits_ok={res['payload_bits_ok']}")
        print(f"sharded_mixed/vmap,{res['vmap_per_query_us']:.1f},")
        out = "BENCH_pr5" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "sharded_mixed_batches",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    section("# ISSUE-2: amortized rvset cache + batched queries (Table-2 "
            "cfg)", amortized)
    section("# ISSUE-3: incremental cache maintenance under edge deltas",
            incremental)
    section("# ISSUE-4: unified session, mixed-kind fused batches",
            session_bench)
    def scaleout():
        res = pe.exp_scaleout(n=int(320 * scale) + 80,
                              m=int(1280 * scale) + 320,
                              n_q=24 if fast else 48)
        for row in res["rows"]:
            print(f"scaleout/k{row['k']}_fpd{row['fragments_per_device']},"
                  f"{row['per_query_us']:.1f},"
                  f"qps={row['queries_per_sec']:.0f};"
                  f"wire_bits={row['wire_bits_total']};"
                  f"answers_match={row['answers_match']};"
                  f"payload_bits_ok={row['payload_bits_ok']}")
        out = "BENCH_pr6" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "scaleout_fragments_per_device",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    def chaos_bench():
        res = pe.exp_chaos(n=int(160 * scale) + 8, m=int(480 * scale) + 8,
                           rounds=6 if fast else 12,
                           per_round=9 if fast else 15)
        print("chaos/p95_per_query,"
              f"{res['p95_per_query_us']:.1f},"
              f"p50={res['p50_per_query_us']:.1f};"
              f"success_rate={res['success_rate']:.3f};"
              f"answers_ok={res['answers_ok']};"
              f"retries={res['retries']};"
              f"rollbacks={res['rollbacks']};"
              f"degraded_groups={res['degraded_groups']}")
        out = "BENCH_pr7" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "chaos_serving",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    def async_serve():
        res = exp_async_serve(n=int(800 * scale) + 100,
                              m=int(3200 * scale) + 400,
                              n_q=96 if fast else 240,
                              open_loop_n=48 if fast else 120,
                              repeats=2 if fast else 3)
        print(f"async_serve/continuous,{1e6 / res['async_qps']:.1f},"
              f"qps={res['async_qps']:.0f};"
              f"throughput_ratio={res['throughput_ratio']:.2f};"
              f"answers_ok={res['answers_ok']}")
        print(f"async_serve/sync_drain,{1e6 / res['sync_qps']:.1f},"
              f"qps={res['sync_qps']:.0f}")
        ol = res["open_loop"]
        print(f"async_serve/open_loop,{ol['p99_ms'] * 1e3:.1f},"
              f"p50_ms={ol['p50_ms']:.1f};p95_ms={ol['p95_ms']:.1f};"
              f"p99_ms={ol['p99_ms']:.1f};"
              f"offered_qps={ol['offered_qps']:.0f};"
              f"occupancy={ol['batch_occupancy']:.2f}")
        out = "BENCH_pr8" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "async_continuous_batching",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    def mvcc_bench():
        res = exp_mvcc(n=int(800 * scale) + 100,
                       m=int(3200 * scale) + 400,
                       n_events=64 if fast else 160)
        for mix, row in res["mixes"].items():
            print(f"mvcc/{mix}_barrier,{row['barrier']['read_p95_ms'] * 1e3:.1f},"
                  f"read_p95_ms={row['barrier']['read_p95_ms']:.1f};"
                  f"update_p95_ms={row['barrier']['update_p95_ms']:.1f}")
            print(f"mvcc/{mix}_mvcc,{row['mvcc']['read_p95_ms'] * 1e3:.1f},"
                  f"read_p95_ms={row['mvcc']['read_p95_ms']:.1f};"
                  f"update_p95_ms={row['mvcc']['update_p95_ms']:.1f};"
                  f"read_p95_ratio={row['read_p95_ratio']:.2f}")
        print(f"mvcc/summary,0.0,"
              f"read_p95_ratio_min={res['read_p95_ratio_min']:.2f};"
              f"answers_ok={res['answers_ok']};"
              f"offered_qps={res['offered_qps']:.0f}")
        out = "BENCH_pr9" + suffix
        with open(out, "w") as f:
            json.dump({"experiment": "mvcc_snapshot_serving",
                       "fast_mode": fast, **res}, f, indent=2)
        print(f"# wrote {out}")

    section("# ISSUE-5: sharded one-collective batches, all query kinds",
            sharded_mixed)
    section("# ISSUE-6: k >> d scale-out, fragments packed per device",
            scaleout)
    section("# ISSUE-7: fault-tolerant serving under a seeded 1% fault "
            "schedule", chaos_bench)
    section("# ISSUE-8: continuous-batching async serving vs the sync "
            "drain pattern", async_serve)
    section("# ISSUE-9: MVCC non-blocking deltas vs the barrier write "
            "path", mvcc_bench)

    if failures:
        print(f"# FAILED sections ({len(failures)}): {failures}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
