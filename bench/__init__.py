"""Chip benchmark of the speculative serving path.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the machine it is started on.
Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel cost lives in a file of its own under this directory,
found by the name ``BENCHMARK.json`` gives it.
"""
