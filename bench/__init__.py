"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own (``configs/``, ``traffic/``,
``metrics/``), found by the name ``BENCHMARK.json`` gives it.
"""
