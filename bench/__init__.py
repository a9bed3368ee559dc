"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; cells, configurations, traffic mixes and
per-layer metrics are found by name from ``BENCHMARK.json``."""
