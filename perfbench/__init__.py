"""The chip benchmark of the incremental engine.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
line.  Configurations (``configs/``), traffic mixes (``traffic/``), job
kinds (``jobs/``) and per-layer metric readers (``metrics/``) are each
found by name from files of their own, so a new cell is new files only.
"""
