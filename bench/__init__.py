"""The chip benchmark: one data-driven harness over the serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json``.  Each configuration, traffic mix and
metric is a file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name that ``BENCHMARK.json`` gives it.
"""
