"""On-chip benchmark of the served relational path.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip it is
started on.  Everything that belongs to one configuration, architecture,
traffic mix or per-layer metric is a file of its own under this directory,
found by the name that ``BENCHMARK.json`` or the configuration gives it
(``spec.py``).
"""
