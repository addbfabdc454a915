"""On-chip benchmark of the serving stack: cells, traffic, metrics.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it finds and prints
one JSON result line.  See ``bench/harness.py``.
"""
