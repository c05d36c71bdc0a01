"""The on-chip benchmark of the served query path (see ``BENCHMARK.json``
and ``PERF.md``): ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``."""
