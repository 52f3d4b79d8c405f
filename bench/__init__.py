"""On-chip serving benchmark: one harness driven by the data files beside it.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one model
configuration (``configs/``), one traffic mix (``mixes/``), one cell's
correctness limits (``limits/``) or one per-layer metric (``metrics/``)
sits in a file of its own, found by the name that ``BENCHMARK.json`` gives.
"""
