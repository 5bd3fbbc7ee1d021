"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` drives one model configuration under one traffic mix through
the served path (gRPC client -> frontend -> core -> batcher -> model) on the
chip this process opens, and prints one JSON line.  Everything that decides a
number lives here: traffic generation, the peaks table, the FLOP count, the
reduction from trace and counters to metrics, the plain reference and the
comparison behind ``correct``.  See ``PERF.md`` for how to add a
configuration, a cell or a per-layer metric by files alone.
"""
