"""On-chip benchmark of the repository: one cell of ``BENCHMARK.json`` per
run of ``bench/run.py``.  Everything the yardstick needs (traffic,
references, FLOP counts, peaks, trace reduction) lives in this package and
imports nothing from the program except the system under test."""
