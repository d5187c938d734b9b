"""Chip benchmark of the async basis-rotation training job.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once on the accelerator it is started on.
Everything that belongs to one configuration, cell, traffic mix or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives it
(`bench/spec.py`).
"""
