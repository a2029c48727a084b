"""A configuration file -> the program's ``ArchConfig``, and its weights.

A configuration file (``configs/<name>.json``) holds the published model
config's keys as they are run, the keys changed from the source under
``reduced``, and a ``serving`` block with the deployment's geometry. The
weights are the benchmark's own: normal draws from ``--seed``, made on the
device in one jitted call, in the layout the program takes (layers stacked
on a leading axis, RMSNorm weights stored as offsets from 1, vocabulary
rows padded to a multiple of 256).

``base_weights`` are the published model's weights, which the plain
reference reads with the configuration's own equations. The program's
decoder has no scalar multipliers and an untied LM head, so
``init_weights`` folds Granite's multipliers into the bf16 weights, as a
checkpoint converted for it would: the embedding times
``embedding_multiplier``, ``w_q`` times ``attention_multiplier`` over the
program's 1/sqrt(head_dim), ``w_o`` and every ``w_down`` times
``residual_multiplier``, and a head of the (tied) embedding's transpose
over ``logits_scaling``. Each is linear, so the program computes the
published model.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
VOCAB_PAD = 256


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


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
    from repro.configs.base import ArchConfig
    serving = conf["serving"]
    e = n_experts(conf)
    kw = {}
    if e:
        kw = dict(num_experts=e, experts_top_k=conf["num_experts_per_tok"],
                  moe_capacity_factor=serving["moe_capacity_factor"],
                  moe_group_size=serving["moe_group_size"])
    return ArchConfig(
        name=conf["name"], family="moe" if e else "dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=head_dim(conf),
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        vocab_pad_to=VOCAB_PAD, **kw)


def weight_shapes(conf: dict) -> dict:
    """{path: (shape, dtype name, std)} of every published weight: normal
    draws with std 1/sqrt(fan_in), ``w_q`` and ``w_k`` times the file's
    ``weights.qk_gain`` (default 1), the embedding with its
    ``weights.embed_std`` (default 1), the router with its
    ``weights.router_logit_std`` over sqrt(fan_in) (unit-variance inputs
    then give router logits of that std), and no head where the
    embedding is tied; std 0 marks an RMSNorm offset (zeros: weight 1)."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 head_dim(conf))
    f, vp, e = conf["intermediate_size"], padded_vocab(conf), n_experts(conf)
    w = conf.get("weights", {})
    router = w.get("router_logit_std", 1.0)
    qk = w.get("qk_gain", 1.0)
    b = "layers/b0/"
    out = {
        "embed": ((vp, d), "bfloat16", w.get("embed_std", 1.0)),
        "final_norm": ((d,), "float32", 0.0),
        b + "norm1": ((L, d), "float32", 0.0),
        b + "norm2": ((L, d), "float32", 0.0),
        b + "attn/w_q": ((L, d, H * hd), "bfloat16", qk * d ** -0.5),
        b + "attn/w_k": ((L, d, KV * hd), "bfloat16", qk * d ** -0.5),
        b + "attn/w_v": ((L, d, KV * hd), "bfloat16", d ** -0.5),
        b + "attn/w_o": ((L, H * hd, d), "bfloat16", (H * hd) ** -0.5),
    }
    ffn = "moe/" if e else "mlp/"
    lead = (L, e) if e else (L,)
    out.update({
        b + ffn + "w_gate": (lead + (d, f), "bfloat16", d ** -0.5),
        b + ffn + "w_up": (lead + (d, f), "bfloat16", d ** -0.5),
        b + ffn + "w_down": (lead + (f, d), "bfloat16", f ** -0.5),
    })
    if e:
        out[b + "moe/router"] = ((L, d, e), "float32", router * d ** -0.5)
    if not conf.get("tie_word_embeddings"):
        out["head"] = ((d, vp), "bfloat16", d ** -0.5)
    return out


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
    """The program's weights for the published ``base`` weights: the
    configuration's scalar multipliers folded in, a head for a tied
    embedding. Each leaf keeps its dtype."""
    import jax.numpy as jnp

    def scaled(x, m):
        return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)

    res = conf.get("residual_multiplier", 1.0)
    q_mult = (conf["attention_multiplier"] * head_dim(conf) ** 0.5
              if "attention_multiplier" in conf else 1.0)
    blk = dict(base["layers"]["b0"])
    attn = dict(blk["attn"], w_q=scaled(blk["attn"]["w_q"], q_mult),
                w_o=scaled(blk["attn"]["w_o"], res))
    ffn = "moe" if "moe" in blk else "mlp"
    blk.update(attn=attn, **{ffn: dict(blk[ffn], w_down=scaled(
        blk[ffn]["w_down"], res))})
    head = base["embed"].T if conf.get("tie_word_embeddings") \
        else base["head"]
    return dict(base, layers={"b0": blk},
                embed=scaled(base["embed"],
                             conf.get("embedding_multiplier", 1.0)),
                head=scaled(head, 1.0 / conf.get("logits_scaling", 1.0)))


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
