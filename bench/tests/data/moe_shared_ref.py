"""Plain float32 reference of the test-only ``moe_shared`` architecture:
``bench/references/llama_moe.py``'s decoder with a shared SwiGLU expert
whose output is added to the routed experts' in every layer. The tests
copy this file to ``references/moe_shared.py``."""
import math

import jax
import jax.numpy as jnp

from bench.references.llama_moe import (
    F32, _attention, _ffn, _head, _proj, _rms, _rope)


def _shared(h, m, fp8):
    g = _proj("td,df->tf", h, m["w_gate"], fp8, None)
    u = _proj("td,df->tf", h, m["w_up"], fp8, None)
    return _proj("tf,fd->td", jax.nn.silu(g) * u, m["w_down"], fp8, None)


def make_forward(conf: dict, fp8: bool = False):
    """jit fn(params, tokens (T,) int32, rows (n,) int32) -> the logits
    (n, vocab_size) f32 at positions ``rows``, causal over ``tokens``."""
    d, H, KV = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    hd = int(conf.get("head_dim") or d // H)
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    scale = conf.get("attention_multiplier", 1.0 / math.sqrt(hd))
    emb_mult = conf.get("embedding_multiplier", 1.0)
    res_mult = conf.get("residual_multiplier", 1.0)
    logit_div = conf.get("logits_scaling", 1.0)
    vocab = conf["vocab_size"]
    tied = bool(conf.get("tie_word_embeddings"))

    def layer(x, lw):
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _rms(x, lw["norm1"], eps)
        a = lw["attn"]
        q = _proj("td,dn->tn", h, a["w_q"], fp8, None).reshape(T, H, hd)
        k = _proj("td,dn->tn", h, a["w_k"], fp8, None).reshape(T, KV, hd)
        v = _proj("td,dn->tn", h, a["w_v"], fp8, None).reshape(T, KV, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q.reshape(T, KV, H // KV, hd), k, v, scale)
        x = x + res_mult * _proj("tn,nd->td", o, a["w_o"], fp8, None)
        h = _rms(x, lw["norm2"], eps)
        y = _ffn(h, lw, conf, fp8) + _shared(h, lw["moe"]["shared"], fp8)
        return x + res_mult * y, None

    def forward(params, tokens, rows):
        x = params["embed"][tokens].astype(F32) * emb_mult
        x, _ = jax.lax.scan(layer, x, params["layers"]["b0"])
        hsel = _rms(x[rows], params["final_norm"], eps)
        head = params["embed"].T if tied else params["head"]
        return _head(hsel, head, vocab) / logit_div

    return jax.jit(forward)
