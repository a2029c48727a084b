"""On-chip serving benchmark: one harness driven by data files.

``BENCHMARK.json`` at the root names the cells. Each cell names a model
configuration (``configs/<name>.json``, which names its architecture
module ``archs/<arch>.py`` and its plain reference
``references/<reference>.py``) and a traffic mix
(``traffic/<name>.json``); each per-layer metric is one reader in
``metrics/<name>.py``. The harness is ``run.py``.
"""
