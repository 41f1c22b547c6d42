"""The benchmark of the served matchmaking path, driven by data.

``BENCHMARK.json`` at the repository root names the cells. A cell is one
configuration (``benchmark/configs/<name>.json``: the service settings,
the chips, the waiting pool) under one traffic mix
(``benchmark/traffic/<name>.json``: the arrival and rating model). Each
per-layer metric has a reader of its own (``benchmark/metrics/<name>.py``).
A cell, configuration or metric is added by adding files and entries; no
code here names one.

Run one cell from the repository root:

    python3 -m benchmark.run --workload elo_1v1.search --seed 7 \\
        --seconds 10 --trace 0

The yardstick lives here and nowhere in the program: the traffic
generator, the plain reference that decides ``correct``, the table of
peaks, the byte count of a formation window and the trace reduction.
"""
