"""The work a prefill or a decode step needs, from a configuration file.

These count what the algorithm requires, whatever implements it:

* FLOPs: 2 per multiply-add of every weight a token uses (the attention
  projections, the router and the k routed experts, or the dense MLP; the
  LM head only where a logit is produced), plus causal attention
  (2 x 2 x heads x head_dim per visible key).
* Bytes of a decode step: every weight the step must read once (weights
  in bf16, the router in f32), where a mixture of experts reads only the
  experts its B live tokens route to, E(1 - (1 - k/E)^B) in expectation
  with uniform routing, and the K and V of every live position.

Embedding lookups and norms are left out: they are a rounding error here.
"""
from __future__ import annotations

from typing import Iterable

BF16, F32 = 2, 4


def _dims(conf: dict):
    d, H, KV = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    hd = int(conf.get("head_dim") or d // H)
    return d, H, KV, hd


def attn_params(conf: dict) -> int:
    d, H, KV, hd = _dims(conf)
    return 2 * d * H * hd + 2 * d * KV * hd


def expert_params(conf: dict) -> int:
    """One expert's (or the dense MLP's) SwiGLU weights."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def router_params(conf: dict) -> int:
    return conf["hidden_size"] * conf.get("num_local_experts", 0)


def active_layer_params(conf: dict) -> int:
    """Weights one token multiplies by in one layer."""
    k = conf.get("num_experts_per_tok", 1) if conf.get("num_local_experts") \
        else 1
    return attn_params(conf) + router_params(conf) + k * expert_params(conf)


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def kv_bytes_per_token(conf: dict) -> int:
    _, _, KV, hd = _dims(conf)
    return 2 * conf["num_hidden_layers"] * KV * hd * BF16


def attention_flops(conf: dict, visible_keys: int) -> int:
    """Score and value products of one query row over ``visible_keys``,
    across every layer."""
    _, H, _, hd = _dims(conf)
    return 4 * H * hd * visible_keys * conf["num_hidden_layers"]


def prefill_flops(conf: dict, prompt_len: int) -> int:
    """One prompt of ``prompt_len`` tokens, causal, with the logits of its
    last position."""
    T, L = prompt_len, conf["num_hidden_layers"]
    return (2 * active_layer_params(conf) * L * T
            + attention_flops(conf, T * (T + 1) // 2)
            + 2 * head_params(conf))


def expected_experts(conf: dict, tokens: int) -> float:
    """Distinct experts that ``tokens`` tokens route to, in expectation."""
    e = conf.get("num_local_experts", 0)
    if not e:
        return 1.0
    k = conf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def decode_flops(conf: dict, positions: Iterable[int]) -> int:
    """One decode step; ``positions`` holds each live slot's position
    (the new token's index; it attends to ``position + 1`` keys)."""
    pos = list(positions)
    L = conf["num_hidden_layers"]
    return (2 * active_layer_params(conf) * L * len(pos)
            + attention_flops(conf, sum(p + 1 for p in pos))
            + 2 * head_params(conf) * len(pos))


def decode_bytes(conf: dict, positions: Iterable[int]) -> float:
    """Bytes one decode step must move: weights read once, KV of every
    live position read."""
    pos = list(positions)
    if not pos:
        return 0.0
    L = conf["num_hidden_layers"]
    weights = (attn_params(conf) * BF16 + router_params(conf) * F32
               + expected_experts(conf, len(pos)) * expert_params(conf) * BF16)
    return (L * weights + head_params(conf) * BF16
            + kv_bytes_per_token(conf) * sum(p + 1 for p in pos))


def bound_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
