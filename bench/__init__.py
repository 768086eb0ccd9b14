"""Chip benchmark of the serving engine, driven by the data files beside it.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it finds.  Everything that
belongs to one configuration (``configs/``), traffic mix (``mixes/``),
cell (``cells/``) or per-layer metric (``metrics/``) lives in a file of its
own that the harness finds by name.
"""
