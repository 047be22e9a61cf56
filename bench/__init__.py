"""The benchmark of the served point-cloud path (`python bench/run.py`).

Everything that defines the yardstick lives here: traffic generation,
the model configurations and their plain reference, the work counts, the
peak table, the profiler-trace reduction and one reader per per-layer
metric.  `BENCHMARK.json` at the repository root names the cells.
"""
