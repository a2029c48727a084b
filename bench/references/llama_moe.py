"""Plain float32 reference of a llama-style decoder.

Pre-norm RMSNorm blocks; grouped-query attention with rotate-half RoPE and
a causal softmax; a SwiGLU feed-forward that is either one dense MLP or a
dropless top-k mixture of experts (softmax over the k chosen router logits,
every routed expert applied to every token that chose it); a final RMSNorm
and an LM head, tied to the embedding where ``tie_word_embeddings`` says
so. The scalar multipliers of Granite's equations
(``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``) are read from the
configuration file, defaulting to a plain llama decoder.

It imports nothing of the program. Every matrix product runs in float32 at
``Precision.HIGHEST``. With ``fp8=True`` the operands of every projection
and expert matrix (not the router, not the LM head) are rounded to
float8_e4m3 first, weights per tensor (per expert) and activations per
row: the precision step below the bf16 the configurations state, used as
the correctness check's control.

Weights arrive as the published model's (``bench/model.py``'s
``base_weights``), in the benchmark's layout: stacked on a leading layer
axis, RMSNorm weights stored as offsets from 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn
Q_CHUNK = 512            # query rows per attention block
HEAD_CHUNK_MAX = 16384   # vocabulary columns per LM-head block


def _fp8(x, axes):
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _proj(expr, x, w, fp8, w_axes):
    w = w.astype(F32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, w_axes)
    return jnp.einsum(expr, x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale):
    """Causal GQA. q (T, KV, G, hd); k, v (T, KV, hd) -> (T, KV*G*hd)."""
    T, KV, G, hd = q.shape
    c = min(Q_CHUNK, T)
    qc = q.reshape(T // c, c, KV, G, hd)
    kpos = jnp.arange(T)

    def block(args):
        qb, i = args
        s = jnp.einsum("ckgd,skd->kgcs", qb, k, precision=HI) * scale
        qpos = i * c + jnp.arange(c)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgcs,skd->ckgd", p, v, precision=HI)

    o = jax.lax.map(block, (qc, jnp.arange(T // c)))
    return o.reshape(T, KV * G * hd)


def _ffn(h, lw, conf, fp8):
    if "moe" not in lw:
        m = lw["mlp"]
        g = _proj("td,df->tf", h, m["w_gate"], fp8, None)
        u = _proj("td,df->tf", h, m["w_up"], fp8, None)
        return _proj("tf,fd->td", jax.nn.silu(g) * u, m["w_down"], fp8, None)
    m = lw["moe"]
    k = conf["num_experts_per_tok"]
    logits = jnp.einsum("td,de->te", h, m["router"].astype(F32),
                        precision=HI)
    top, idx = jax.lax.top_k(logits, k)
    wts = jax.nn.softmax(top, axis=-1)
    mix = jnp.zeros_like(logits).at[jnp.arange(h.shape[0])[:, None],
                                    idx].set(wts)
    g = _proj("td,edf->tef", h, m["w_gate"], fp8, (1, 2))
    u = _proj("td,edf->tef", h, m["w_up"], fp8, (1, 2))
    y = _proj("tef,efd->ted", jax.nn.silu(g) * u, m["w_down"], fp8, (1, 2))
    return jnp.einsum("te,ted->td", mix, y, precision=HI)


def _head(hsel, head, vocab):
    d, vp = head.shape
    n = next(n for n in range(1, vp + 1)
             if vp % n == 0 and vp // n <= HEAD_CHUNK_MAX)
    c = vp // n

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * c, c, axis=1)
        return jnp.dot(hsel, w.astype(F32), precision=HI)

    out = jax.lax.map(block, jnp.arange(n))           # (n, rows, c)
    return jnp.moveaxis(out, 0, 1).reshape(hsel.shape[0], vp)[:, :vocab]


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
        return x + res_mult * _ffn(h, lw, conf, fp8), None

    def forward(params, tokens, rows):
        x = params["embed"][tokens].astype(F32) * emb_mult
        x, _ = jax.lax.scan(layer, x, params["layers"]["b0"])
        hsel = _rms(x[rows], params["final_norm"], eps)
        head = params["embed"].T if tied else params["head"]
        return _head(hsel, head, vocab) / logit_div

    return jax.jit(forward)
