"""On-chip benchmark of legate_sparse_tpu, driven by BENCHMARK.json.

``benchmark/run.py`` runs one cell once.  Everything a cell needs is
found by name: its configuration under ``configs/``, its traffic mix
under ``traffic/``, the module of the mix's ``op`` under ``ops/``, and
one reader per metric under ``metrics/``.
"""
