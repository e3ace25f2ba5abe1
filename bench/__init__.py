"""The on-chip benchmark: one command runs one cell (``bench/run.py``).
See ``BENCHMARK.json`` for the cells and ``PERF.md`` for what they
measure."""
