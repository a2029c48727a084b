"""On-chip serving benchmark: one harness driven by data files.

``BENCHMARK.json`` at the root names the cells. Each cell names a model
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each per-layer metric is one reader in
``metrics/<name>.py``. The harness is ``run.py``.
"""
