"""The on-chip benchmark of the wave engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chip and prints one JSON
result line.  Cells, configurations, traffic mixes and per-layer metrics
are data: a configuration is ``configs/<name>.json``, a mix
``mixes/<name>.json`` and a per-layer metric ``metrics/<name>.py``, all
found by the names that ``BENCHMARK.json`` gives.
"""
