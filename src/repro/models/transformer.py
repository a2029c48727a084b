"""Unified decoder stack for every assigned architecture.

The layer stack is a ``lax.scan`` over *super-layers* (the repeating block
pattern from ``ArchConfig.superlayer_pattern``), with parameters stacked on
the leading axis — HLO size is independent of depth, which is what makes the
95/126-layer dry-runs compile fast. Hybrid stacks (zamba2) additionally have
a non-scanned tail and a parameter-shared attention block closed over by the
scan body.

Three entry points:
  ``forward``      — logits for training (and prefill cache collection)
  ``prefill``      — forward + per-layer decode caches
  ``decode_step``  — one token, cache update (serving)

Parameters are plain nested dicts; ``params_shape`` produces the
ShapeDtypeStruct twin via ``jax.eval_shape`` so 405B-parameter dry-runs never
allocate.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import execution as ex
from repro.models import attention as attn_mod
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rk
from repro.models.layers import (
    DEFAULT_RT, RuntimeCfg, _init, dense, embed_tokens, init_attn, init_mlp,
    lm_logits, rms_norm, swiglu_mlp,
)

Params = Dict[str, Any]

# Block kinds whose decode KV cache moves into the paged pool. ``attn_local``
# keeps its rolling-window buffer (already O(window), paging buys nothing)
# and SSM/linear-attention state stays slot-indexed (constant size per slot —
# the allocator accounts it as a "state block", core/paging.py).
PAGED_KINDS = ("attn_dense", "attn_global", "attn_moe", "shared_attn")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(kind: str, key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("attn_dense", "attn_local", "attn_global"):
        return {"norm1": jnp.zeros((d,), jnp.float32),
                "attn": init_attn(k1, cfg, dtype),
                "norm2": jnp.zeros((d,), jnp.float32),
                "mlp": init_mlp(k2, cfg, dtype)}
    if kind == "attn_moe":
        return {"norm1": jnp.zeros((d,), jnp.float32),
                "attn": init_attn(k1, cfg, dtype),
                "norm2": jnp.zeros((d,), jnp.float32),
                "moe": moe_mod.init_moe(k2, cfg, dtype)}
    if kind == "mamba2":
        return {"norm1": jnp.zeros((d,), jnp.float32),
                "mamba": m2.init_mamba2(k1, cfg, dtype)}
    if kind == "rwkv6":
        return {"norm1": jnp.zeros((d,), jnp.float32),
                "norm2": jnp.zeros((d,), jnp.float32),
                "rwkv": rk.init_rwkv6(k1, cfg, dtype)}
    if kind == "shared_attn":
        return {}                      # params live in params["shared_attn"]
    raise ValueError(kind)


def _init_superlayer(key, cfg: ArchConfig, dtype) -> Params:
    pat = cfg.superlayer_pattern
    keys = jax.random.split(key, len(pat))
    return {f"b{i}": _init_block(kind, keys[i], cfg, dtype)
            for i, kind in enumerate(pat)}


def init_params(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    d, vp = cfg.d_model, cfg.padded_vocab
    k_embed, k_head, k_layers, k_shared, k_tail = jax.random.split(key, 5)

    n_super = cfg.num_superlayers
    layer_keys = jax.random.split(k_layers, n_super)
    layers = jax.vmap(lambda k: _init_superlayer(k, cfg, dtype))(layer_keys)

    params: Params = {
        "embed": _init(k_embed, (vp, d), dtype, scale=1.0),
        "head": _init(k_head, (d, vp), dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "layers": layers,
    }
    if "shared_attn" in cfg.superlayer_pattern:
        ks1, ks2 = jax.random.split(k_shared)
        params["shared_attn"] = {
            "norm1": jnp.zeros((d,), jnp.float32),
            "attn": init_attn(ks1, cfg, dtype),
            "norm2": jnp.zeros((d,), jnp.float32),
            "mlp": init_mlp(ks2, cfg, dtype),
        }
    n_tail = cfg.hybrid_tail_layers
    if n_tail:
        tail_keys = jax.random.split(k_tail, n_tail)
        params["tail"] = jax.vmap(
            lambda k: _init_block("mamba2", k, cfg, dtype))(tail_keys)
    return params


def params_shape(cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    """ShapeDtypeStruct twin of ``init_params`` — no allocation."""
    return jax.eval_shape(
        lambda k: init_params(k, cfg, dtype), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Block application (training / prefill)
# ---------------------------------------------------------------------------

def _apply_block(kind: str, x, p: Params, cfg: ArchConfig, rt: RuntimeCfg,
                 shared: Optional[Params], collect_cache: bool):
    """Returns (x, aux, cache_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind == "shared_attn":
        p = shared
    window = cfg.window_size if kind == "attn_local" else 0

    if kind in ("attn_dense", "attn_local", "attn_global", "attn_moe",
                "shared_attn"):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if collect_cache:
            a, (k, v) = attn_mod.attention_block(
                h, p["attn"], cfg, rt, window=window, return_kv=True)
            cache = _kv_to_cache(k, v, window)
        else:
            a = attn_mod.attention_block(h, p["attn"], cfg, rt, window=window)
        x = x + a
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "attn_moe":
            mo, aux = moe_mod.moe_mlp(h, p["moe"], cfg, rt)
            x = x + mo
        else:
            x = x + swiglu_mlp(h, p["mlp"], cfg, rt)
        return x, aux, cache

    if kind == "mamba2":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if collect_cache:
            o, (hs, conv) = m2.mamba2_block_with_state(h, p["mamba"], cfg, rt)
            cache = {"h": hs, "conv": conv}
        else:
            o = m2.mamba2_block(h, p["mamba"], cfg, rt)
        return x + o, aux, cache

    if kind == "rwkv6":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if collect_cache:
            o, (S, prev_tm) = rk.rwkv6_block_with_state(h, p["rwkv"], cfg, rt)
        else:
            o = rk.rwkv6_block(h, p["rwkv"], cfg, rt)
        x = x + o
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + rk.rwkv6_channel_mix(h2, p["rwkv"], cfg, rt)
        if collect_cache:
            cache = {"S": S, "prev_tm": prev_tm, "prev_cm": h2[:, -1:, :]}
        return x, aux, cache

    raise ValueError(kind)


def _kv_to_cache(k: jax.Array, v: jax.Array, window: int) -> Params:
    """Build a decode cache from prefill K/V (B, S, kv, hd).

    The ``pos`` buffer is per-sequence (B, S): continuous-batching slots
    advance independently, so each row tracks its own written positions.
    """
    b, s, kvh, hd = k.shape
    if not window or s < window:
        pos = jnp.arange(s, dtype=jnp.int32)
        return {"k": k, "v": v,
                "pos": jnp.broadcast_to(pos, (b, s))}
    # rolling window cache: slot j holds the token p in [s-window, s) with
    # p % window == j (so decode can keep writing at pos % window).
    p = jnp.arange(s - window, s, dtype=jnp.int32)
    slots = p % window
    kc = jnp.zeros((b, window, kvh, hd), k.dtype).at[:, slots].set(
        k[:, s - window:])
    vc = jnp.zeros((b, window, kvh, hd), v.dtype).at[:, slots].set(
        v[:, s - window:])
    posc = jnp.zeros((window,), jnp.int32).at[slots].set(p)
    return {"k": kc, "v": vc, "pos": jnp.broadcast_to(posc, (b, window))}


# ---------------------------------------------------------------------------
# Forward / prefill
# ---------------------------------------------------------------------------

def _superlayer_fn(cfg: ArchConfig, rt: RuntimeCfg, shared: Optional[Params],
                   collect_cache: bool):
    pat = cfg.superlayer_pattern

    def body(x, p_super):
        aux_total = jnp.zeros((), jnp.float32)
        caches = {}
        for i, kind in enumerate(pat):
            x, aux, cache = _apply_block(kind, x, p_super[f"b{i}"], cfg, rt,
                                         shared, collect_cache)
            aux_total = aux_total + aux
            if collect_cache:
                caches[f"b{i}"] = cache if cache is not None else {}
        return x, (aux_total, caches) if collect_cache else (aux_total, {})
    return body


def _run_stack(params: Params, x: jax.Array, cfg: ArchConfig, rt: RuntimeCfg,
               collect_cache: bool):
    shared = params.get("shared_attn")
    body = _superlayer_fn(cfg, rt, shared, collect_cache)

    if cfg.remat == "full":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    from repro.models.layers import shard_tag

    def scan_body(carry, p_super):
        x, aux = carry
        x = shard_tag(rt, x, "act_btd")      # re-anchor GSPMD each superlayer
        x, (aux_i, caches) = body(x, p_super)
        return (x, aux + aux_i), caches

    (x, aux), caches = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"])

    tail_caches = None
    if "tail" in params:
        n_tail = cfg.hybrid_tail_layers
        tail_caches = []
        for i in range(n_tail):
            p_i = jax.tree.map(lambda a: a[i], params["tail"])
            x, _, c = _apply_block("mamba2", x, p_i, cfg, rt, None,
                                   collect_cache)
            tail_caches.append(c if c is not None else {})
        if collect_cache:
            tail_caches = jax.tree.map(
                lambda *xs: jnp.stack(xs), *tail_caches) if tail_caches else {}
    return x, aux, caches, tail_caches


def forward(params: Params, inputs: jax.Array, cfg: ArchConfig,
            rt: RuntimeCfg = DEFAULT_RT) -> Tuple[jax.Array, jax.Array]:
    """inputs: (B, S) int tokens or (B, S, d) embeddings.
    Returns (logits (B, S, Vp) f32, aux_loss)."""
    x, aux = forward_hidden(params, inputs, cfg, rt)
    logits = lm_logits(x, params["head"], cfg.vocab_size,
                       policy=ex.policy_from(cfg, rt))
    return logits, aux


def forward_hidden(params: Params, inputs: jax.Array, cfg: ArchConfig,
                   rt: RuntimeCfg = DEFAULT_RT) -> Tuple[jax.Array, jax.Array]:
    """Backbone only: final normed hidden (B, S, d) + aux. The train loss
    fuses the LM head per-chunk (runtime/train_loop.py) so the full f32
    (B, S, V) logits tensor is never materialized."""
    if inputs.ndim == 2:
        x = embed_tokens(inputs, params["embed"]).astype(rt.act_dtype)
    else:
        x = inputs.astype(rt.act_dtype)
    x, aux, _, _ = _run_stack(params, x, cfg, rt, collect_cache=False)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def prefill(params: Params, inputs: jax.Array, cfg: ArchConfig,
            rt: RuntimeCfg = DEFAULT_RT):
    """Returns (last_token_logits (B, Vp), caches)."""
    if inputs.ndim == 2:
        x = embed_tokens(inputs, params["embed"]).astype(rt.act_dtype)
    else:
        x = inputs.astype(rt.act_dtype)
    x, _, caches, tail_caches = _run_stack(params, x, cfg, rt,
                                           collect_cache=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(x[:, -1], params["head"], cfg.vocab_size,
                       policy=ex.policy_from(cfg, rt))
    out_caches = {"layers": caches}
    if tail_caches is not None:
        out_caches["tail"] = tail_caches
    return logits, out_caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _decode_block(kind: str, x, p: Params, cache: Params, pos,
                  cfg: ArchConfig, rt: RuntimeCfg, shared: Optional[Params],
                  page_map=None, layer=None):
    """Returns (x, new_cache). With ``page_map`` (B, max_pages), the
    PAGED_KINDS blocks read the stacked pooled paged cache at super-layer
    ``layer`` instead of the dense per-slot one, and return the step's
    new rows (:func:`_paged_decode_attn`)."""
    if kind == "shared_attn":
        p = shared
    window = cfg.window_size if kind == "attn_local" else 0

    if kind in ("attn_dense", "attn_local", "attn_global", "attn_moe",
                "shared_attn"):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if page_map is not None and kind in PAGED_KINDS:
            a, new_kv = _paged_decode_attn(h, p["attn"], cache, layer, pos,
                                           page_map, cfg, rt)
        else:
            a, new_kv = _decode_attn(h, p["attn"], cache, pos, cfg, rt,
                                     window)
        x = x + a
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "attn_moe":
            mo, _ = moe_mod.moe_mlp(h, p["moe"], cfg, rt)
            x = x + mo
        else:
            x = x + swiglu_mlp(h, p["mlp"], cfg, rt)
        return x, new_kv

    if kind == "mamba2":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        o, (hs, conv) = m2.mamba2_decode(h, p["mamba"], cfg,
                                         (cache["h"], cache["conv"]), rt)
        return x + o, {"h": hs, "conv": conv}

    if kind == "rwkv6":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        o, (S, prev_tm) = rk.rwkv6_decode(h, p["rwkv"], cfg,
                                          (cache["S"], cache["prev_tm"]), rt)
        x = x + o
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        o2, prev_cm = rk.rwkv6_channel_mix_decode(h2, p["rwkv"], cfg,
                                                  cache["prev_cm"], rt)
        x = x + o2
        return x, {"S": S, "prev_tm": prev_tm, "prev_cm": prev_cm}

    raise ValueError(kind)


def _decode_attn(x, p, cache, pos, cfg: ArchConfig, rt: RuntimeCfg,
                 window: int):
    from repro.models.layers import batched_einsum, shard_tag
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    # ``pos`` may be a scalar (lockstep decode) or (B,) — continuous
    # batching tracks an independent position per slot.
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = dense(x, p["w_q"], cfg, rt, "q").reshape(b, 1, h, hd)
    k = dense(x, p["w_k"], cfg, rt, "k").reshape(b, 1, kvh, hd)
    v = dense(x, p["w_v"], cfg, rt, "v").reshape(b, 1, kvh, hd)
    q = attn_mod.apply_rope(q, posb[:, None], cfg.rope_theta)
    k = attn_mod.apply_rope(k, posb[:, None], cfg.rope_theta)
    # flash-decoding sharding: q is tiny — replicate it over "model" so the
    # seq-sharded cache is contracted IN PLACE (partial scores + psum of the
    # (b, h, hd) output) instead of GSPMD all-gathering the whole cache to
    # match head-sharded q (measured: 2×1 GiB/layer on llama3-405b).
    q = shard_tag(rt, q, "decode_q")

    kc, vc, posc = cache["k"], cache["v"], cache["pos"]
    smax = kc.shape[1]
    slot = posb % smax if window else posb              # (b,) write rows
    bidx = jnp.arange(b)
    kc = kc.at[bidx, slot].set(k[:, 0].astype(kc.dtype))
    vc = vc.at[bidx, slot].set(v[:, 0].astype(vc.dtype))
    posc = posc.at[bidx, slot].set(posb)

    scale = hd ** -0.5
    # GQA kept grouped: (b, 1, kv, g, hd) × (b, s, kv, hd) — no broadcast
    # materialization of the expanded cache, no f32 operand upcast.
    q5 = q.reshape(b, kvh, g, hd)
    s = batched_einsum("bkgd,bskd->bkgs", q5, kc, rt,
                       out_dtype=jnp.float32) * scale     # (b, kv, g, s)
    # posc=-1 marks unwritten (or freed-slot) rows; each slot only attends
    # to rows its own occupant wrote at positions <= its own pos.
    valid = (posc >= 0) & (posc <= posb[:, None])        # (b, smax)
    if window:
        valid &= posc > posb[:, None] - window
    else:
        valid &= jnp.arange(smax)[None, :] <= posb[:, None]
    s = jnp.where(valid[:, None, None, :], s, attn_mod.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = batched_einsum("bkgs,bskd->bkgd", pr.astype(vc.dtype), vc, rt,
                       out_dtype=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    out = dense(o, p["w_o"], cfg, rt, "o")
    return out, {"k": kc, "v": vc, "pos": posc}


def _page_slot(posb, page_map, trash: int, page_size: int):
    """(physical page, in-page offset) of row ``posb`` of each slot.

    Idle slots (current page entry ``-1``) are routed to the *trash* page
    (the pool's last page, owned by no slot) so live pages are never
    aliased. Positions at/past the table's capacity (``max_pages *
    page_size == max_len``) go there too rather than alias the clipped
    last page — the dense path drops such out-of-bounds rows. Plain
    decode never reaches them (the host finishes a slot at max_len), but
    a k>1 speculative verify probes a few positions past the end of an
    almost-full slot."""
    mp = page_map.shape[1]
    lpage = jnp.clip(posb // page_size, 0, mp - 1)
    off = posb % page_size
    phys = jnp.take_along_axis(page_map, lpage[:, None], axis=1)[:, 0]
    phys = jnp.where((phys >= 0) & (posb < mp * page_size), phys, trash)
    return phys, off


def _paged_decode_attn(x, p, cache, layer, pos, page_map, cfg: ArchConfig,
                       rt: RuntimeCfg):
    """Decode attention over the pooled paged cache.

    ``cache`` holds every super-layer's k/v pools, of which this reads
    ``layer`` — ``(n_super, n_pages+1, page_size, kvh*hd)`` — and this
    layer's pos pool ``(n_pages+1, page_size)``; ``page_map`` is
    ``(B, max_pages)`` int32 (``-1`` = unallocated). The last physical
    page is a *trash* page owned by no slot: gathers of unallocated
    logical pages read from it — its rows are never attended to because
    an unallocated logical page's row indices all exceed the slot's
    ``pos`` (tables are prefixes, core/paging.py) and the causal
    ``arange <= pos`` mask kills them.

    The pools are read-only here. k/v are gathered from the stack
    directly: a per-layer slice of a pool would be materialized first
    (134 MB a layer on the DeepSeek stage). The pos pool is stored
    pages-minor, and handling the whole stack relayouts it, so the scan
    slices it. The current token's k/v/pos go into the *gathered*
    per-slot copy at row ``pos`` (dropped past ``max_len``, as the dense
    scatter drops them), and the new k/v rows ``(B, kvh*hd)`` and
    positions ``(B,)`` are returned for :func:`paged_decode_step` to
    write into every layer's pool in one scatter after the layer scan.

    Exactness contract: the gather reconstructs each slot's KV in the
    *identical* ``(B, max_len, ...)`` layout the dense path uses (row i
    holds position i; ``max_pages * page_size == max_len``), with the
    current row written as the dense path writes it, then runs the
    *same* mask/softmax/einsum code — masked rows are the same NEG_INF
    constant in both, their softmax weight underflows to exactly 0.0,
    and 0 × finite garbage is 0, so paged greedy decode is
    token-for-token identical to dense.
    """
    from repro.models.layers import batched_einsum, shard_tag
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = dense(x, p["w_q"], cfg, rt, "q").reshape(b, 1, h, hd)
    k = dense(x, p["w_k"], cfg, rt, "k").reshape(b, 1, kvh, hd)
    v = dense(x, p["w_v"], cfg, rt, "v").reshape(b, 1, kvh, hd)
    q = attn_mod.apply_rope(q, posb[:, None], cfg.rope_theta)
    k = attn_mod.apply_rope(k, posb[:, None], cfg.rope_theta)
    q = shard_tag(rt, q, "decode_q")

    kp, vc_pool, pp = cache["k"], cache["v"], cache["pos"]
    ps = kp.shape[2]
    mp = page_map.shape[1]
    smax = mp * ps
    trash = kp.shape[1] - 1
    page_map = jnp.asarray(page_map, jnp.int32)

    # gather into the dense (b, max_len, ...) layout, then write the
    # current token at row pos of the gathered copy
    safe = jnp.where(page_map >= 0, page_map, trash)       # (b, mp)
    k_row = k[:, 0].reshape(b, kvh * hd).astype(kp.dtype)
    v_row = v[:, 0].reshape(b, kvh * hd).astype(vc_pool.dtype)
    bidx = jnp.arange(b)
    kc = kp[layer, safe].reshape(b, smax, kvh * hd) \
        .at[bidx, posb].set(k_row, mode="drop").reshape(b, smax, kvh, hd)
    vc = vc_pool[layer, safe].reshape(b, smax, kvh * hd) \
        .at[bidx, posb].set(v_row, mode="drop").reshape(b, smax, kvh, hd)
    posc = pp[safe].reshape(b, smax).at[bidx, posb].set(posb, mode="drop")

    # from here: byte-identical to the dense _decode_attn arithmetic
    scale = hd ** -0.5
    q5 = q.reshape(b, kvh, g, hd)
    s = batched_einsum("bkgd,bskd->bkgs", q5, kc, rt,
                       out_dtype=jnp.float32) * scale
    valid = (posc >= 0) & (posc <= posb[:, None])
    valid &= jnp.arange(smax)[None, :] <= posb[:, None]
    s = jnp.where(valid[:, None, None, :], s, attn_mod.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = batched_einsum("bkgs,bskd->bkgd", pr.astype(vc.dtype), vc, rt,
                       out_dtype=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    out = dense(o, p["w_o"], cfg, rt, "o")
    return out, {"k": k_row, "v": v_row, "pos": posb}


def decode_step(params: Params, tokens: jax.Array, caches: Params, pos,
                cfg: ArchConfig, rt: RuntimeCfg = DEFAULT_RT):
    """One decoding step. tokens: (B, 1) int32; pos: scalar int32 (lockstep
    — same position for all sequences) or (B,) int32 (continuous batching —
    each slot decodes at its own position; see runtime/serve_loop.py).
    Returns (logits (B, Vp) f32, new_caches)."""
    x = embed_tokens(tokens, params["embed"]).astype(rt.act_dtype)
    shared = params.get("shared_attn")
    pat = cfg.superlayer_pattern

    from repro.models.layers import shard_tag

    def scan_body(carry, inp):
        x = carry
        p_super, cache_super = inp
        x = shard_tag(rt, x, "act_btd")
        new_caches = {}
        for i, kind in enumerate(pat):
            x, nc = _decode_block(kind, x, p_super[f"b{i}"],
                                  cache_super[f"b{i}"], pos, cfg, rt, shared)
            new_caches[f"b{i}"] = nc
        return x, new_caches

    x, new_layer_caches = jax.lax.scan(
        scan_body, x, (params["layers"], caches["layers"]))

    new_caches = {"layers": new_layer_caches}
    if "tail" in params:
        n_tail = cfg.hybrid_tail_layers
        tails = []
        for i in range(n_tail):
            p_i = jax.tree.map(lambda a: a[i], params["tail"])
            c_i = jax.tree.map(lambda a: a[i], caches["tail"])
            x, nc = _decode_block("mamba2", x, p_i, c_i, pos, cfg, rt, None)
            tails.append(nc)
        new_caches["tail"] = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(x[:, 0], params["head"], cfg.vocab_size,
                       policy=ex.policy_from(cfg, rt))
    return logits, new_caches


def paged_decode_step(params: Params, tokens: jax.Array, caches: Params,
                      pos, page_map: jax.Array, cfg: ArchConfig,
                      rt: RuntimeCfg = DEFAULT_RT):
    """``decode_step`` over a paged cache (``init_paged_cache`` layout).

    ``page_map`` (B, max_pages) int32 is shared by every layer — one
    physical page id names the same rows in each layer's pool — so it is
    closed over by the scan body rather than scanned, and so are the
    PAGED_KINDS k/v pools: the scan only reads them, at its layer index
    (pos pools are scanned), and emits each layer's new k/v rows and
    positions; one scatter after the scan writes them into the stacked
    pools at ``(layer, page, offset)`` — in place when the caller
    donates ``caches``. Tail blocks and non-PAGED_KINDS leaves behave
    exactly as in ``decode_step``."""
    x = embed_tokens(tokens, params["embed"]).astype(rt.act_dtype)
    shared = params.get("shared_attn")
    pat = cfg.superlayer_pattern

    from repro.models.layers import shard_tag

    paged = [f"b{i}" for i, kind in enumerate(pat) if kind in PAGED_KINDS]
    pools = caches["layers"]
    scanned = {n: {"pos": c["pos"]} if n in paged else c
               for n, c in pools.items()}

    def scan_body(carry, inp):
        x = carry
        p_super, cache_super, layer = inp
        x = shard_tag(rt, x, "act_btd")
        new_caches = {}
        for i, kind in enumerate(pat):
            name = f"b{i}"
            cache = cache_super[name]
            if name in paged:
                cache = dict(cache, k=pools[name]["k"], v=pools[name]["v"])
            x, new_caches[name] = _decode_block(
                kind, x, p_super[name], cache, pos, cfg, rt, shared,
                page_map=page_map, layer=layer)
        return x, new_caches

    x, new_layer_caches = jax.lax.scan(
        scan_body, x, (params["layers"], scanned,
                       jnp.arange(cfg.num_superlayers)))

    b = tokens.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    page_map = jnp.asarray(page_map, jnp.int32)
    layer = jnp.arange(cfg.num_superlayers)[:, None]
    for name in paged:
        pool, rows = pools[name], new_layer_caches[name]
        phys, off = _page_slot(posb, page_map, pool["k"].shape[1] - 1,
                               pool["k"].shape[2])
        # index the layer too, so each update is one contiguous row
        at = (layer, phys[None], off[None])
        new_layer_caches[name] = {
            key: pool[key].at[at].set(rows[key]) for key in pool}

    new_caches = {"layers": new_layer_caches}
    if "tail" in params:
        n_tail = cfg.hybrid_tail_layers
        tails = []
        for i in range(n_tail):
            p_i = jax.tree.map(lambda a: a[i], params["tail"])
            c_i = jax.tree.map(lambda a: a[i], caches["tail"])
            x, nc = _decode_block("mamba2", x, p_i, c_i, pos, cfg, rt, None)
            tails.append(nc)
        new_caches["tail"] = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(x[:, 0], params["head"], cfg.vocab_size,
                       policy=ex.policy_from(cfg, rt))
    return logits, new_caches


# ---------------------------------------------------------------------------
# Speculative multi-token verify (draft-and-verify decode; core/speculative)
# ---------------------------------------------------------------------------

def _rollback_caches(snaps, n_acc, posb, cfg: ArchConfig, page_map=None):
    """Select the committed cache after a k-step verify pass.

    ``snaps[j]`` is the full cache tree after verify step ``j``, so
    ``snaps[n_acc[i]]`` is slot ``i``'s last *committed* state. Rather
    than replay, the rollback treats the two cache-leaf classes
    differently:

    * **append leaves** — k/v/pos of the ``PAGED_KINDS`` attention
      caches. Row (or page offset) ``posb + j`` holds only step ``j``'s
      write, so the final snapshot is kept and rejected rows
      ``> posb + n_acc`` are scrubbed back to the init sentinel (pos
      ``-1``, k/v ``0``) — identical to what an unwritten row holds, so
      over-scrubbing rows that were never written is a value no-op.
    * **state leaves** — rolling-window KV (``attn_local``), mamba2 /
      rwkv6 recurrent state, and the hybrid tail. Steps overwrite these
      in place (a rejected write destroys history that masking cannot
      recover), so the per-step snapshots are stacked on a new leading
      axis and each slot gathers the snapshot at its accepted count.

    The stack materializes append leaves too, but those stacked copies
    are never consumed, so XLA dead-code-eliminates them under jit.
    """
    k = len(snaps)
    final = snaps[-1]
    b = posb.shape[0]
    append_blocks = {f"b{i}" for i, kind in enumerate(cfg.superlayer_pattern)
                     if kind in PAGED_KINDS}
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *snaps)

    def fix(path, f, st):
        keys = [str(getattr(p, "key", p)) for p in path]
        if (keys[0] == "layers" and keys[1] in append_blocks
                and keys[-1] in ("k", "v", "pos")):
            zero = jnp.asarray(-1 if keys[-1] == "pos" else 0, f.dtype)
            if page_map is None:
                # dense layout (n_super, B, max_len, ...): mask-scrub the
                # rejected rows (row index == position).
                smax = f.shape[2]
                scrub = jnp.arange(smax, dtype=jnp.int32)[None, :] \
                    > (posb + n_acc)[:, None]                    # (B, smax)
                scrub = scrub.reshape((1, b, smax) + (1,) * (f.ndim - 3))
                return jnp.where(scrub, zero, f)
            # pooled layout (n_super, pages+1, page_size, ...): scatter-
            # scrub each rejected step's (page, offset) row. Accepted
            # steps and unmapped/out-of-range positions are redirected to
            # the trash page (duplicate trash writes are fine — the
            # scrubbed value is a constant).
            ps = f.shape[2]
            trash = f.shape[1] - 1
            for j in range(1, k):
                phys, off = _page_slot(posb + j, page_map, trash, ps)
                phys = jnp.where(j > n_acc, phys, trash)
                f = f.at[:, phys, off].set(zero)
            return f
        # state leaf: stacked (k, n_axis, B, ...) -> per-slot snapshot
        moved = jnp.moveaxis(st, 2, 0)                       # (B, k, n, ...)
        idx = n_acc.reshape((b, 1) + (1,) * (moved.ndim - 2))
        idx = jnp.broadcast_to(idx, (b, 1) + moved.shape[2:])
        sel = jnp.take_along_axis(moved, idx, axis=1)[:, 0]  # (B, n, ...)
        return jnp.moveaxis(sel, 0, 1)

    return jax.tree_util.tree_map_with_path(fix, final, stacked)


def _multi_decode(params: Params, tokens_seq: jax.Array, caches: Params,
                  pos, active, cfg: ArchConfig, rt: RuntimeCfg,
                  page_map=None):
    b, k = tokens_seq.shape
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    cur = caches
    snaps = []
    greedy = []
    for j in range(k):
        tok = tokens_seq[:, j:j + 1].astype(jnp.int32)
        if page_map is None:
            logits, cur = decode_step(params, tok, cur, posb + j, cfg, rt)
        else:
            logits, cur = paged_decode_step(params, tok, cur, posb + j,
                                            page_map, cfg, rt)
        greedy.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        snaps.append(cur)
    g = jnp.stack(greedy, axis=1)                            # (B, k)
    if k == 1:
        return g[:, 0:1], g, jnp.zeros((b,), jnp.int32), cur
    match = (tokens_seq[:, 1:].astype(jnp.int32) == g[:, :-1])
    n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    # idle (free) slots must behave like plain decode — exactly one write
    # at their parked position, which admission overwrites — so drafts
    # are never accepted for them.
    n_acc = jnp.where(jnp.asarray(active, bool), n_acc, 0)
    next_tok = jnp.take_along_axis(g, n_acc[:, None], axis=1)
    new_caches = _rollback_caches(snaps, n_acc, posb, cfg, page_map=page_map)
    return next_tok, g, n_acc, new_caches


def multi_decode_step(params: Params, tokens_seq: jax.Array, caches: Params,
                      pos, active, cfg: ArchConfig,
                      rt: RuntimeCfg = DEFAULT_RT):
    """Score k candidate tokens in ONE jitted pass (speculative verify).

    ``tokens_seq`` (B, k) carries each slot's next input token followed
    by k-1 draft tokens; ``pos`` (B,) is each slot's decode position and
    ``active`` (B,) bool marks occupied slots. Step ``j`` runs the exact
    ``decode_step`` computation at ``pos + j``, so its argmax ``g[:, j]``
    is *precisely* what plain greedy decode would emit after committing
    the first ``j`` candidates. The accepted count ``n_acc`` is the
    longest prefix of drafts matching those argmaxes, which makes the
    committed tokens ``g[:, :n_acc+1]`` provably identical to plain
    greedy decode — the exactness contract speculative serving pins.

    Returns ``(next_tokens (B, 1), greedy (B, k), n_acc (B,),
    new_caches)`` with rejected-token cache writes rolled back
    (:func:`_rollback_caches`)."""
    return _multi_decode(params, tokens_seq, caches, pos, active, cfg, rt)


def paged_multi_decode_step(params: Params, tokens_seq: jax.Array,
                            caches: Params, pos, active,
                            page_map: jax.Array, cfg: ArchConfig,
                            rt: RuntimeCfg = DEFAULT_RT):
    """``multi_decode_step`` over a paged cache: rejected pool writes are
    scrubbed in-jit, so the allocator can release over-grown pages
    afterwards without touching device memory (``PageAllocator.
    trim_slot``)."""
    return _multi_decode(params, tokens_seq, caches, pos, active, cfg, rt,
                         page_map=page_map)


# ---------------------------------------------------------------------------
# Cache init (zeros / shape-only)
# ---------------------------------------------------------------------------

def _block_cache(kind: str, batch: int, max_len: int, cfg: ArchConfig,
                 dtype=jnp.bfloat16):
    if kind in ("attn_dense", "attn_global", "attn_moe", "shared_attn"):
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, max_len, kvh, hd), dtype),
                "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
                "pos": jnp.full((batch, max_len), -1, jnp.int32)}
    if kind == "attn_local":
        w = min(cfg.window_size, max_len)
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, w, kvh, hd), dtype),
                "v": jnp.zeros((batch, w, kvh, hd), dtype),
                "pos": jnp.full((batch, w), -1, jnp.int32)}
    if kind == "mamba2":
        h, conv = m2.init_mamba2_state(batch, cfg)
        return {"h": h, "conv": conv}
    if kind == "rwkv6":
        d = cfg.d_model
        nh = d // cfg.ssm_head_dim
        return {"S": jnp.zeros((batch, nh, cfg.ssm_head_dim,
                                cfg.ssm_head_dim), jnp.float32),
                "prev_tm": jnp.zeros((batch, 1, d), dtype),
                "prev_cm": jnp.zeros((batch, 1, d), dtype)}
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    pat = cfg.superlayer_pattern
    n_super = cfg.num_superlayers

    def one_super():
        return {f"b{i}": _block_cache(kind, batch, max_len, cfg, dtype)
                for i, kind in enumerate(pat)}

    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(), one_super())
    caches = {"layers": stacked}
    n_tail = cfg.hybrid_tail_layers
    if n_tail:
        tail = _block_cache("mamba2", batch, max_len, cfg, dtype)
        caches["tail"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_tail,) + a.shape).copy(), tail)
    return caches


def cache_shape(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Params:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))


def init_paged_cache(cfg: ArchConfig, batch: int, max_len: int,
                     page_size: int, pages: int,
                     dtype=jnp.bfloat16) -> Params:
    """Paged twin of ``init_cache``: PAGED_KINDS leaves become pools of
    ``pages + 1`` physical pages (the extra one is the trash page, see
    ``_paged_decode_attn``) of ``page_size`` rows each, shared by all
    slots; everything else (window caches, SSM state, tail) stays
    slot-indexed dense. Requires ``max_len % page_size == 0`` so the
    gathered layout matches the dense one row-for-row.

    A k/v row is stored as one ``kvh * hd`` vector: with ``(kvh, hd)``
    minor dims (8, 64 on granite) the TPU lays a pool out pages-minor to
    avoid lane padding, and every page gather or row scatter would then
    relayout the whole pool."""
    if max_len % page_size:
        raise ValueError(f"max_len={max_len} not a multiple of "
                         f"page_size={page_size}")
    pat = cfg.superlayer_pattern
    n_super = cfg.num_superlayers
    kvh, hd = cfg.num_kv_heads, cfg.head_dim

    def one_block(kind):
        if kind in PAGED_KINDS:
            p1 = pages + 1
            return {"k": jnp.zeros((p1, page_size, kvh * hd), dtype),
                    "v": jnp.zeros((p1, page_size, kvh * hd), dtype),
                    "pos": jnp.full((p1, page_size), -1, jnp.int32)}
        return _block_cache(kind, batch, max_len, cfg, dtype)

    one_super = {f"b{i}": one_block(kind) for i, kind in enumerate(pat)}
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_super,) + a.shape).copy(), one_super)
    caches = {"layers": stacked}
    n_tail = cfg.hybrid_tail_layers
    if n_tail:
        tail = _block_cache("mamba2", batch, max_len, cfg, dtype)
        caches["tail"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_tail,) + a.shape).copy(), tail)
    return caches


# ---------------------------------------------------------------------------
# Standalone super-layer entry points (roofline per-layer cost lowering —
# cost_analysis counts scan bodies once, so launch/dryrun.py lowers ONE
# super-layer separately and scales; see launch/roofline.py).
# ---------------------------------------------------------------------------

def superlayer_params_slice(params_or_shapes: Params) -> Params:
    """First super-layer's (unstacked) params — works on shapes too."""
    def take0(a):
        if hasattr(a, "shape"):
            if isinstance(a, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
            return a[0]
        return a
    return jax.tree.map(take0, params_or_shapes["layers"])


def superlayer_forward(x: jax.Array, p_super: Params,
                       shared: Optional[Params], cfg: ArchConfig,
                       rt: RuntimeCfg):
    """One (possibly rematted) super-layer forward: x -> (x', aux)."""
    from repro.models.layers import shard_tag
    x = shard_tag(rt, x, "act_btd")          # same anchor as the scan body
    body = _superlayer_fn(cfg, rt, shared, collect_cache=False)
    if cfg.remat == "full":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, (aux, _) = body(x, p_super)
    return x, aux


def superlayer_train_cost(x: jax.Array, ct: jax.Array, p_super: Params,
                          shared: Optional[Params], cfg: ArchConfig,
                          rt: RuntimeCfg):
    """fwd+bwd of one super-layer (the per-layer train-cost probe).

    ``ct`` is the output cotangent; returns grads wrt (x, p_super, shared)."""
    def scalar(x, p_super, shared):
        y, aux = superlayer_forward(x, p_super, shared, cfg, rt)
        return jnp.sum(y.astype(jnp.float32) * ct.astype(jnp.float32)) + aux
    argnums = (0, 1) if shared is None else (0, 1, 2)
    return jax.grad(scalar, argnums=argnums)(x, p_super, shared)


def superlayer_decode(x: jax.Array, p_super: Params, cache_super: Params,
                      pos, shared: Optional[Params], cfg: ArchConfig,
                      rt: RuntimeCfg):
    """One decode super-layer step: (x, cache) -> (x', cache')."""
    pat = cfg.superlayer_pattern
    new_caches = {}
    for i, kind in enumerate(pat):
        x, nc = _decode_block(kind, x, p_super[f"b{i}"], cache_super[f"b{i}"],
                              pos, cfg, rt, shared)
        new_caches[f"b{i}"] = nc
    return x, new_caches


def superlayer_cache_slice(cache_or_shapes: Params) -> Params:
    def take0(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
        return a[0]
    return jax.tree.map(take0, cache_or_shapes["layers"])
