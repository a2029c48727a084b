"""The work a prefill or a decode step needs, from a configuration file.

These count what the algorithm requires, whatever implements it:

* FLOPs: 2 per multiply-add of every weight a token uses (in a mixture of
  experts the router and the k routed experts; the LM head only where a
  logit is produced), plus attention's score and value products
  (2 x 2 x heads x head_dim per visible key).
* Bytes of a decode step: every weight the step must read once (weights
  in bf16, a router in f32), where a mixture of experts reads only the
  experts its B live tokens route to, E(1 - (1 - k/E)^B) in expectation
  with uniform routing; the cache of every live position that attention
  reads; and every live slot's fixed-size state.

The architecture module of the configuration (``bench/archs/``) says which
layers hold what; the block counts below are the vocabulary it counts in.
Embedding lookups and norms are left out: they are a rounding error here.
"""
from __future__ import annotations

from typing import Iterable

from bench import archs

BF16, F32 = 2, 4


def dims(conf: dict):
    d, H, KV = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    hd = int(conf.get("head_dim") or d // H)
    return d, H, KV, hd


def attn_params(conf: dict) -> int:
    d, H, KV, hd = dims(conf)
    return 2 * d * H * hd + 2 * d * KV * hd


def expert_params(conf: dict) -> int:
    """One expert's (or the dense MLP's) SwiGLU weights."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def router_params(conf: dict) -> int:
    return conf["hidden_size"] * conf.get("num_local_experts", 0)


def active_layer_params(conf: dict) -> int:
    """Weights one token multiplies by in one attention and SwiGLU (or
    mixture of experts) layer."""
    k = conf.get("num_experts_per_tok", 1) if conf.get("num_local_experts") \
        else 1
    return attn_params(conf) + router_params(conf) + k * expert_params(conf)


def head_params(conf: dict) -> int:
    return conf["hidden_size"] * conf["vocab_size"]


def expected_experts(conf: dict, tokens: int) -> float:
    """Distinct experts that ``tokens`` tokens route to, in expectation."""
    e = conf.get("num_local_experts", 0)
    if not e:
        return 1.0
    k = conf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def kv_bytes_per_token(conf: dict) -> int:
    """The cache one position adds, over every layer."""
    return archs.of(conf).kv_bytes_per_token(conf)


def attention_flops(conf: dict, positions: Iterable[int]) -> int:
    """Score and value products of query rows at ``positions``, across
    every layer."""
    return archs.of(conf).attention_flops(conf, positions)


def prefill_flops(conf: dict, prompt_len: int) -> int:
    """One prompt of ``prompt_len`` tokens, causal, with the logits of its
    last position."""
    arch = archs.of(conf)
    return (sum(arch.layer_flops(conf)) * prompt_len
            + arch.attention_flops(conf, range(prompt_len))
            + 2 * head_params(conf))


def decode_flops(conf: dict, positions: Iterable[int]) -> int:
    """One decode step; ``positions`` holds each live slot's position
    (the new token's index; it attends to ``position + 1`` keys)."""
    pos = list(positions)
    arch = archs.of(conf)
    return (sum(arch.layer_flops(conf)) * len(pos)
            + arch.attention_flops(conf, pos)
            + 2 * head_params(conf) * len(pos))


def decode_bytes(conf: dict, positions: Iterable[int]) -> float:
    """Bytes one decode step must move: weights read once, the cache of
    every live position read, every live slot's state read and written."""
    pos = list(positions)
    if not pos:
        return 0.0
    arch = archs.of(conf)
    return (arch.decode_weight_bytes(conf, len(pos))
            + arch.kv_bytes(conf, pos)
            + 2 * arch.state_bytes_per_slot(conf) * len(pos))


def bound_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
