"""On-chip benchmark of the system's cells (``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything a cell needs is found by
name: ``configs/<config>.json`` (the deployment), ``traffic/<mix>.json``
(the load), ``metrics/<metric>.py`` (one reducer per per-layer metric).
The rest of this package is the yardstick: data and weights from the
seed (``data``), the plain reference (``reference``), the load generators
(``loads``), the trace reduction (``trace``), operation and byte counts
(``roofline``) and the peaks table (``peaks.json``).
"""
