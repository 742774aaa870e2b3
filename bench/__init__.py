"""On-chip benchmark of the SSD-offloaded fine-tuning and serving paths.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json``; see :mod:`bench.run`.
"""
