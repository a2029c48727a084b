"""A configuration file -> the program's ``ArchConfig``, and its weights.

A configuration file (``configs/<name>.json``) holds the published model
config's keys as they are run, the keys changed from the source under
``reduced``, its architecture module under ``arch`` (``bench/archs/``;
``llama`` where absent), and a ``serving`` block with the deployment's
geometry. The weights are the benchmark's own: normal draws from
``--seed``, made on the device in one jitted call, in the layout the
program takes (layers stacked on a leading axis, RMSNorm weights stored as
offsets from 1, vocabulary rows padded to a multiple of 256).

``base_weights`` are the published model's weights, which the plain
reference reads with the configuration's own equations; ``init_weights``
are the program's, the architecture module's ``fold`` of them (scalar
multipliers folded in, as a checkpoint converted for the program would
be). The shapes, the fold and the ``ArchConfig`` come from the module.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import archs

BENCH = Path(__file__).resolve().parent
VOCAB_PAD = 256


def load_config(name: str, root: Path = BENCH) -> dict:
    """The configuration file ``<root>/configs/<name>.json``; its
    architecture module is loaded from ``<root>/archs/``, else from
    ``bench/archs/``."""
    conf = json.loads((root / "configs" / f"{name}.json").read_text())
    archs.load(archs.name_of(conf), root)
    return conf


def head_dim(conf: dict) -> int:
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def padded_vocab(conf: dict) -> int:
    v = conf["vocab_size"]
    return (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


def n_experts(conf: dict) -> int:
    return int(conf.get("num_local_experts", 0))


def jax_seed(seed: int) -> int:
    """A 31-bit key seed from any whole number (JAX keys take int32)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this file."""
    return archs.of(conf).arch_config(conf)


def weight_shapes(conf: dict) -> dict:
    """{path: (shape, dtype name, std)} of every published weight; std 0
    marks an RMSNorm offset (zeros: weight 1)."""
    return archs.of(conf).weight_shapes(conf)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def _draw(conf: dict, key):
    import jax
    import jax.numpy as jnp
    shapes = weight_shapes(conf)
    keys = jax.random.split(key, len(shapes))
    flat = {}
    for k, (path, (shape, dtype, std)) in zip(keys, sorted(shapes.items())):
        flat[path] = (jax.random.normal(k, shape, jnp.float32)
                      * std).astype(dtype)
    return _nest(flat)


def fold(conf: dict, base: dict) -> dict:
    """The program's weights for the published ``base`` weights. Each leaf
    keeps its dtype."""
    return archs.of(conf).fold(conf, base)


def _made(conf: dict, seed: int, fn):
    import jax
    params = jax.jit(fn)(jax.random.PRNGKey(jax_seed(seed)))
    return jax.block_until_ready(params)


def base_weights(conf: dict, seed: int):
    """The published model's weights from ``seed``, on the default device,
    in one jit: what the reference reads."""
    return _made(conf, seed, lambda key: _draw(conf, key))


def init_weights(conf: dict, seed: int):
    """The program's weights from ``seed``, on the default device, in one
    jit: ``fold`` of ``base_weights``."""
    return _made(conf, seed, lambda key: fold(conf, _draw(conf, key)))
