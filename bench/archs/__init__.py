"""Architecture modules: what the harness knows of one model family.

A configuration file names its module under ``"arch"``; where the key is
absent the module is ``llama``. ``load_config`` finds the module as
``archs/<arch>.py`` in the benchmark directory the file came from, else
in this package. A module provides:

* ``arch_config(conf)``: the program's ``ArchConfig``;
* ``weight_shapes(conf)``: {path: (shape, dtype name, std)} of every
  published weight, drawn from the seed in sorted path order;
* ``fold(conf, base)``: the program's weights for the published ones;
* ``layer_kinds(conf)``: the program's block kind of each layer, in order;
* ``layer_flops(conf)``: the FLOPs one token needs in each layer's weights;
* ``attention_flops(conf, positions)``: score and value FLOPs of query
  rows at ``positions`` (a row at position p is the p+1-th token), over
  every layer;
* ``kv_bytes_per_token(conf)``: the cache one position adds;
* ``kv_bytes(conf, positions)``: the cache that query rows at
  ``positions`` read;
* ``state_bytes_per_slot(conf)``: state of fixed size that every live slot
  reads and writes in a decode step (SSM and convolution state; 0 where
  there is none);
* ``decode_weight_bytes(conf, live)``: the weight bytes a decode step of
  ``live`` tokens reads, the LM head among them.

``bench/model.py`` and ``bench/work.py`` dispatch to the module.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent
DEFAULT = "llama"
_loaded: dict = {}


def name_of(conf: dict) -> str:
    return conf.get("arch", DEFAULT)


def load(name: str, bench: Path = HERE.parent):
    """The module ``<bench>/archs/<name>.py``, else this package's; it
    then serves every configuration that names it."""
    path = bench / "archs" / f"{name}.py"
    if not path.exists():
        path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _loaded[name] = mod
    return mod


def of(conf: dict):
    """The architecture module of a configuration."""
    name = name_of(conf)
    return _loaded.get(name) or load(name)
