"""The llama-style decoder: every layer one pre-norm block of grouped-query
attention with RoPE and a SwiGLU feed-forward, which is one dense MLP or a
top-k mixture of SwiGLU experts (``num_local_experts``), with Granite's
scalar multipliers where the file gives them. The module of a
configuration that names none.

The program's decoder has no scalar multipliers and an untied LM head, so
``fold`` folds Granite's multipliers into the bf16 weights, as a
checkpoint converted for it would: the embedding times
``embedding_multiplier``, ``w_q`` times ``attention_multiplier`` over the
program's 1/sqrt(head_dim), ``w_o`` and every ``w_down`` times
``residual_multiplier``, and a head of the (tied) embedding's transpose
over ``logits_scaling``. Each is linear, so the program computes the
published model.

Work: every layer holds K and V of every position and attends to all of
them; a token multiplies by the attention projections, the router and its
k experts (or the MLP); a decode step reads the experts its live tokens
route to (``work.expected_experts``).
"""
from __future__ import annotations

from bench.model import VOCAB_PAD, head_dim, n_experts, padded_vocab
from bench.work import (BF16, F32, active_layer_params, attn_params, dims,
                        expected_experts, expert_params, head_params,
                        router_params)


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


def layer_kinds(conf: dict) -> tuple:
    kind = "attn_moe" if n_experts(conf) else "attn_dense"
    return (kind,) * conf["num_hidden_layers"]


def layer_flops(conf: dict) -> list:
    return [2 * active_layer_params(conf)] * conf["num_hidden_layers"]


def attention_flops(conf: dict, positions) -> int:
    _, H, _, hd = dims(conf)
    return (4 * H * hd * sum(p + 1 for p in positions)
            * conf["num_hidden_layers"])


def kv_bytes_per_token(conf: dict) -> int:
    _, _, KV, hd = dims(conf)
    return 2 * conf["num_hidden_layers"] * KV * hd * BF16


def kv_bytes(conf: dict, positions) -> int:
    return kv_bytes_per_token(conf) * sum(p + 1 for p in positions)


def state_bytes_per_slot(conf: dict) -> int:
    return 0


def decode_weight_bytes(conf: dict, live: int) -> float:
    L = conf["num_hidden_layers"]
    weights = (attn_params(conf) * BF16 + router_params(conf) * F32
               + expected_experts(conf, live) * expert_params(conf) * BF16)
    return L * weights + head_params(conf) * BF16
