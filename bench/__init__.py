"""On-chip benchmark of MRF training.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on and
prints one JSON result line.  Everything that measures lives here: the training
reference's own simulator (``sim``), the plain references (``reference``), the
reduction from trace to metrics (``trace``, ``metrics/``), the peaks
(``peaks``) and the operation and byte counts (``work``).  The program under
test (``src/repro``) is only driven and traced.
"""
