"""On-chip serving benchmark: a data-driven harness over the paged EnginePool path.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the repository root
names the cells, and each configuration, traffic mix, per-layer metric and
correctness limit is a file of its own under this directory.
"""
