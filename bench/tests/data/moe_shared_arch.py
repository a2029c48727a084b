"""Test-only architecture ``moe_shared``: the llama-style mixture of
experts with the program's shared expert (``moe_shared_expert``), one
SwiGLU MLP of ``intermediate_size`` that every token passes through beside
its k routed experts, its output added to theirs. The tests copy this file
to ``archs/moe_shared.py`` of a benchmark directory of their own."""
import dataclasses

from bench.archs import llama
from bench.work import BF16, expert_params

SHARED = "layers/b0/moe/shared/"

layer_kinds = llama.layer_kinds
attention_flops = llama.attention_flops
kv_bytes_per_token = llama.kv_bytes_per_token
kv_bytes = llama.kv_bytes
state_bytes_per_slot = llama.state_bytes_per_slot


def arch_config(conf):
    return dataclasses.replace(llama.arch_config(conf),
                               moe_shared_expert=True)


def weight_shapes(conf):
    L, d, f = (conf["num_hidden_layers"], conf["hidden_size"],
               conf["intermediate_size"])
    return dict(llama.weight_shapes(conf), **{
        SHARED + "w_gate": ((L, d, f), "bfloat16", d ** -0.5),
        SHARED + "w_up": ((L, d, f), "bfloat16", d ** -0.5),
        SHARED + "w_down": ((L, f, d), "bfloat16", f ** -0.5)})


def fold(conf, base):
    """llama's fold, and the shared expert's ``w_down`` times
    ``residual_multiplier`` as the routed experts' are."""
    import jax.numpy as jnp
    out = llama.fold(conf, base)
    res = conf.get("residual_multiplier", 1.0)
    blk = out["layers"]["b0"]
    shared = blk["moe"]["shared"]
    w = shared["w_down"]
    shared = dict(shared, w_down=(w.astype(jnp.float32) * res).astype(
        w.dtype))
    blk = dict(blk, moe=dict(blk["moe"], shared=shared))
    return dict(out, layers={"b0": blk})


def layer_flops(conf):
    return [f + 2 * expert_params(conf) for f in llama.layer_flops(conf)]


def decode_weight_bytes(conf, live):
    return (llama.decode_weight_bytes(conf, live)
            + conf["num_hidden_layers"] * expert_params(conf) * BF16)
