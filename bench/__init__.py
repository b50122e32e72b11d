"""On-chip benchmark of the MPIC serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON line last.
Configurations (``bench/configs``), traffic mixes (``bench/traffic``),
metric readers (``bench/metrics``) and correctness limits
(``bench/limits``) are files found by name.
"""
