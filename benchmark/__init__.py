"""The benchmark of the served matchmaking path, driven by data.

``BENCHMARK.json`` at the repository root names the cells. A cell is one
configuration (``benchmark/configs/<name>.json``: the service settings,
the chips, the waiting pool, and optionally its queue's ``kind``) under
one traffic mix (``benchmark/traffic/<name>.json``: the arrival and rating
model). A queue kind (``benchmark/kinds/<name>.py``) gives the requests'
extra body fields and the plain reference for that kind of queue; a
configuration without one is a 1v1 queue (``benchmark/reference.py``).
Each metric has a reader of its own (``benchmark/metrics/<name>.py``). A
cell, configuration, kind or metric is added by adding files and entries;
no code here names one.

Run one cell from the repository root:

    python3 -m benchmark.run --workload elo_1v1.search --seed 7 \\
        --seconds 10 --trace 0

The yardstick lives here and nowhere in the program: the traffic
generator, the plain references that decide ``correct``, the table of
peaks, the byte count of a formation window and the trace reduction.
"""
