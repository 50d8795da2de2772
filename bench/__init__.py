"""On-chip benchmark of the served router (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once. Everything a cell is made of is data found by name:
``configs/<config>.json`` (the deployment), ``traffic/<traffic>.json`` (the
client mix) and ``metrics/<metric>.py`` (one reader per per-layer metric).
The yardstick (traffic generation, the plain reference, the comparison that
decides ``correct``, the trace reduction, the peaks table and the work
count) lives in ``lib/`` and imports nothing of the program under test.
"""
