"""Paged serving cache: allocator invariants, the dense-equality
exactness contract, and the paged flash-decode kernel.

The contract the whole PR leans on: paging is a memory-*layout* change,
never a numerics change — a paged greedy run must be token-for-token
identical to the dense ``ServeSession`` (solo, multi-tenant, and across
a live migration handoff). The allocator tests pin the host-side
invariants that make that safe: prefix page tables, scrub-before-reuse,
and refusal (not crash) on pool exhaustion.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.paging import PageAllocator, PagesExhausted, pages_for
from repro.models import init_params
from repro.models.layers import RuntimeCfg
from repro.runtime.serve_loop import Request, ServeSession, export_nbytes

RT = RuntimeCfg(ssm_chunk=16)
MAX_LEN = 64
PAGE = 8
MP = MAX_LEN // PAGE


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("llama3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _session(model, slots=4, paged=True, **kw):
    cfg, params = model
    if paged:
        kw.setdefault("page_size", PAGE)
    return ServeSession(params, cfg, batch_slots=slots, max_len=MAX_LEN,
                        rt=RT, paged=paged, **kw)


def _prompts(cfg, n, length=5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, length).astype(np.int32)
            for _ in range(n)]


def _run_all(sess, prompts, max_new=8):
    reqs = [Request(uid=i, prompt=p.copy(), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sess.submit(r)
    sess.run()
    return [r.out for r in reqs]


def _pool_leaves(sess):
    """Yield each paged block's {k, v, pos} pool dict (the leaves whose
    axis-1 is the physical page pool, trash page included)."""
    for blk, leaves in sess.caches["layers"].items():
        pos = leaves.get("pos")
        if pos is not None and pos.ndim == 3 \
                and pos.shape[1] == sess.pages + 1 \
                and pos.shape[2] == sess.page_size:
            yield blk, leaves


# ---------------------------------------------------------------------------
# Allocator (host side, no model)
# ---------------------------------------------------------------------------

def test_alloc_extend_free_roundtrip():
    a = PageAllocator(n_pages=8, page_size=4, max_pages_per_slot=4,
                      n_slots=3)
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1 \
        and pages_for(5, 4) == 2
    p0 = a.alloc_slot(0, 6)                  # 6 tokens -> 2 pages
    assert len(p0) == 2 and a.pages_in_use == 2
    grown = a.extend_slot(0, 9)              # -> 3 pages, 1 new
    assert len(grown) == 1 and a.slot_pages(0) == p0 + grown
    assert a.extend_slot(0, 9) == []         # idempotent: no new pages
    released = a.free_slot(0)
    assert sorted(released) == sorted(p0 + grown)
    assert a.pages_in_use == 0 and a.free_pages == 8
    assert a.slot_pages(0) == []
    # the table is always a logical prefix: page_map pads with -1
    a.alloc_slot(1, 4)
    pm = a.page_map()
    assert pm.shape == (3, 4) and (pm[1, 1:] == -1).all() and pm[1, 0] >= 0
    st = a.stats()
    assert st["allocs"] == 2 and st["frees"] == 1 and st["extends"] == 1
    assert st["utilization"] == 1.0          # 4 tokens in one 4-token page


def test_double_alloc_and_bad_extend_rejected():
    a = PageAllocator(4, 4, 4, 2)
    a.alloc_slot(0, 4)
    with pytest.raises(ValueError):
        a.alloc_slot(0, 4)                   # slot already holds pages
    with pytest.raises(ValueError):
        a.extend_slot(1, 4)                  # empty slot can't extend


def test_out_of_pages_is_refused_not_crashed():
    a = PageAllocator(n_pages=2, page_size=4, max_pages_per_slot=4,
                      n_slots=2)
    assert a.can_admit_tokens(8) and not a.can_admit_tokens(9)
    a.alloc_slot(0, 8)                       # pool now full
    with pytest.raises(PagesExhausted):
        a.alloc_slot(1, 1)
    assert a.stats()["oom_refusals"] == 1
    # slot 1 untouched, slot 0 unharmed, and freeing recovers the pool
    assert a.slot_pages(1) == [] and len(a.slot_pages(0)) == 2
    a.free_slot(0)
    assert a.can_admit_tokens(8)


def test_per_slot_cap_enforced():
    a = PageAllocator(16, 4, 2, 2)
    with pytest.raises(PagesExhausted):
        a.alloc_slot(0, 12)                  # 3 pages > cap 2
    assert not a.can_admit_tokens(12)


def test_free_list_is_lifo():
    a = PageAllocator(4, 4, 4, 2)
    pages = a.alloc_slot(0, 16)
    a.free_slot(0)
    again = a.alloc_slot(1, 16)
    assert again == pages                    # just-freed pages reused first


# ---------------------------------------------------------------------------
# Exactness contract: paged ≡ dense, token for token
# ---------------------------------------------------------------------------

def test_paged_solo_matches_dense(model):
    cfg, _ = model
    (p,) = _prompts(cfg, 1)
    dense = _run_all(_session(model, slots=4, paged=False), [p.copy()])
    paged = _run_all(_session(model, slots=4), [p.copy()])
    assert paged == dense


def test_paged_multi_tenant_matches_dense(model):
    cfg, _ = model
    prompts = _prompts(cfg, 6, seed=1)
    dense = _run_all(_session(model, slots=4, paged=False), prompts)
    paged = _run_all(_session(model, slots=4), prompts)
    assert paged == dense


def test_page_reuse_does_not_leak_stale_kv(model):
    """The LIFO free list hands a freed tenant's pages straight to the
    next occupant — outputs must match a fresh session exactly, which
    fails if free_slot didn't scrub the released pool rows."""
    cfg, _ = model
    pa, pb = _prompts(cfg, 2, seed=2)
    sess = _session(model, slots=1)
    _run_all(sess, [pa])
    # everything returned and the real pages are fully scrubbed (the
    # trash page, pool index `pages`, is scratch by design)
    assert sess.pager.pages_in_use == 0
    found_pool = False
    for _, leaves in _pool_leaves(sess):
        found_pool = True
        assert (np.asarray(leaves["pos"])[:, :-1] == -1).all()
        assert (np.asarray(leaves["k"], np.float32)[:, :-1] == 0).all()
        assert (np.asarray(leaves["v"], np.float32)[:, :-1] == 0).all()
    assert found_pool
    (out_b,) = _run_all(sess, [pb])
    (ref_b,) = _run_all(_session(model, slots=1), [pb.copy()])
    assert out_b == ref_b


def test_admission_refused_when_pool_exhausted(model):
    """A pool with headroom for one resident queues (not crashes) the
    second request and serves it after the first finishes — and the
    outputs still match the per-request dense oracle."""
    cfg, _ = model
    prompts = _prompts(cfg, 2, length=9, seed=3)
    # 9-token prompts need 2 pages at admit and 3 by completion
    # (9 + 8 = 17 tokens); a 3-page pool holds exactly one at a time.
    sess = _session(model, slots=2, pages=3)
    outs = _run_all(sess, prompts, max_new=8)
    assert sess.pager.stats()["oom_refusals"] == 0   # refused via can_admit
    assert sess.pager.stats()["peak_pages_in_use"] <= 3
    ref = [_run_all(_session(model, slots=2, paged=False), [p.copy()],
                    max_new=8)[0] for p in prompts]
    assert outs == ref
    # direct admit without headroom raises the typed refusal
    s2 = _session(model, slots=2, pages=1)
    with pytest.raises(PagesExhausted):
        s2.admit(Request(uid=0, prompt=prompts[0].copy(), max_new=8))


def test_mid_decode_pool_exhaustion_truncates(model):
    """A request that outgrows the pool mid-decode finishes truncated —
    never a crash — and its pages are fully released afterwards."""
    cfg, _ = model
    (p,) = _prompts(cfg, 1, seed=4)
    sess = _session(model, slots=1, pages=1)     # one page: 8 positions
    req = Request(uid=0, prompt=p.copy(), max_new=32)
    sess.submit(req)
    sess.run()
    assert req.done
    assert 0 < len(req.out) < 32                 # truncated, not served
    assert sess.pager.stats()["oom_refusals"] >= 1
    assert sess.pager.pages_in_use == 0          # slot fully released


def test_migration_handoff_mid_request_token_identical(model):
    """Export a slot mid-request, import into a second paged session,
    finish there: outputs equal the uninterrupted dense run, and the
    handoff moves pages-in-use, not slot capacity."""
    cfg, _ = model
    (p,) = _prompts(cfg, 1, seed=5)
    src = _session(model, slots=2)
    dst = _session(model, slots=2)
    req = Request(uid=7, prompt=p.copy(), max_new=12)
    src.admit(req)
    for _ in range(4):
        src.decode_once()
    assert dst.can_accept_pages(src.handoff_pages(0), src.page_size)
    export = src.export_slot(0)
    assert export.pages == src.pager.pages_for(export.pos + 1)
    assert export.page_size == PAGE
    paged_bytes = export_nbytes(export)
    dst.import_slot(export)
    while not req.done:
        dst.decode_once()
    ref = Request(uid=8, prompt=p.copy(), max_new=12)
    dsess = _session(model, slots=2, paged=False)
    dsess.admit(ref)
    while not ref.done:
        dsess.decode_once()
    assert req.out == ref.out
    # O(pages) beats O(max_len): the same handoff through dense sessions
    d_src = _session(model, slots=2, paged=False)
    d_req = Request(uid=9, prompt=p.copy(), max_new=12)
    d_src.admit(d_req)
    for _ in range(4):
        d_src.decode_once()
    dense_bytes = export_nbytes(d_src.export_slot(0))
    assert paged_bytes < dense_bytes


def test_paged_and_dense_sessions_cannot_mix_handoffs(model):
    cfg, _ = model
    (p,) = _prompts(cfg, 1)
    src = _session(model, slots=1)
    src.admit(Request(uid=0, prompt=p.copy(), max_new=8))
    export = src.export_slot(0)
    dst = _session(model, slots=1, paged=False)
    with pytest.raises(ValueError):
        dst.import_slot(export)


def test_jit_cache_key_includes_page_geometry(model):
    """Sessions differing only in page geometry must not share a jitted
    step (the traced cache layout differs)."""
    s1 = _session(model, slots=2, page_size=8)
    s2 = _session(model, slots=2, page_size=16)
    s3 = _session(model, slots=2, page_size=8, pages=4)
    assert s1.step_fn is not s2.step_fn
    assert s1.step_fn is not s3.step_fn


# ---------------------------------------------------------------------------
# In-place pool update: read-only pools in the layer scan, one scatter after
# ---------------------------------------------------------------------------

def _scatter_then_gather_attn(x, p, cache, layer, pos, page_map, cfg, rt):
    """The paged attention the in-place path replaced, kept as the
    reference: write the token into the layer's own pool (``cache``,
    ``layer`` unused), then gather the same pool back out, and return
    the whole pool."""
    import jax.numpy as jnp
    from repro.models import attention as attn_mod
    from repro.models import transformer as tf
    from repro.models.layers import batched_einsum
    b = x.shape[0]
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = tf.dense(x, p["w_q"], cfg, rt, "q").reshape(b, 1, h, hd)
    k = tf.dense(x, p["w_k"], cfg, rt, "k").reshape(b, 1, kvh, hd)
    v = tf.dense(x, p["w_v"], cfg, rt, "v").reshape(b, 1, kvh, hd)
    q = attn_mod.apply_rope(q, posb[:, None], cfg.rope_theta)
    k = attn_mod.apply_rope(k, posb[:, None], cfg.rope_theta)
    kp, vp, pp = cache["k"], cache["v"], cache["pos"]
    ps, mp, trash = kp.shape[1], page_map.shape[1], kp.shape[0] - 1
    smax = mp * ps
    lpage = jnp.clip(posb // ps, 0, mp - 1)
    phys = jnp.take_along_axis(page_map, lpage[:, None], axis=1)[:, 0]
    phys = jnp.where((phys >= 0) & (posb < smax), phys, trash)
    off = posb % ps
    kp = kp.at[phys, off].set(k[:, 0].reshape(b, -1).astype(kp.dtype))
    vp = vp.at[phys, off].set(v[:, 0].reshape(b, -1).astype(vp.dtype))
    pp = pp.at[phys, off].set(posb)
    safe = jnp.where(page_map >= 0, page_map, trash)
    kc = kp[safe].reshape(b, smax, kvh, hd)
    vc = vp[safe].reshape(b, smax, kvh, hd)
    posc = pp[safe].reshape(b, smax)
    s = batched_einsum("bkgd,bskd->bkgs", q.reshape(b, kvh, h // kvh, hd),
                       kc, rt, out_dtype=jnp.float32) * hd ** -0.5
    valid = (posc >= 0) & (posc <= posb[:, None])
    valid &= jnp.arange(smax)[None, :] <= posb[:, None]
    s = jnp.where(valid[:, None, None, :], s, attn_mod.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = batched_einsum("bkgs,bskd->bkgd", pr.astype(vc.dtype), vc, rt,
                       out_dtype=jnp.float32)
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return tf.dense(o, p["w_o"], cfg, rt, "o"), {"k": kp, "v": vp, "pos": pp}


def _scatter_then_gather_step(params, tokens, caches, pos, page_map, cfg,
                              rt):
    """Reference paged decode step: the pools ride through the layer scan
    as xs/ys, each layer writing its pool before gathering it."""
    from repro.models import transformer as tf

    def body(x, inp):
        p_super, c_super = inp
        out = {}
        for i, kind in enumerate(cfg.superlayer_pattern):
            x, out[f"b{i}"] = tf._decode_block(
                kind, x, p_super[f"b{i}"], c_super[f"b{i}"], pos, cfg, rt,
                params.get("shared_attn"), page_map=page_map)
        return x, out

    x = tf.embed_tokens(tokens, params["embed"]).astype(rt.act_dtype)
    x, layers = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
    x = tf.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return tf.lm_logits(x[:, 0], params["head"], cfg.vocab_size), \
        {"layers": layers}


def _filled_pools(cfg, page_map, pos, seed=0):
    """A paged cache whose allocated pages hold random k/v, rows below
    each slot's position marked written (pos = row), the rest unwritten."""
    import jax.numpy as jnp
    from repro.models import init_paged_cache
    b, mp = page_map.shape
    caches = init_paged_cache(cfg, b, MAX_LEN, PAGE, b * mp)
    rng = np.random.default_rng(seed)
    for leaves in caches["layers"].values():
        if "pos" not in leaves or leaves["pos"].shape[1] != b * mp + 1:
            continue
        for key in ("k", "v"):
            leaves[key] = jnp.asarray(
                rng.standard_normal(leaves[key].shape), leaves[key].dtype)
        posp = np.asarray(leaves["pos"]).copy()
        for slot in range(b):
            for lp, page in enumerate(page_map[slot]):
                if page < 0:
                    continue
                rows = lp * PAGE + np.arange(PAGE)
                posp[:, page] = np.where(rows < pos[slot], rows, -1)
        leaves["pos"] = jnp.asarray(posp)
    return caches


def test_inplace_pool_update_matches_scatter_then_gather(model):
    """The read-only-pool step against the scatter-into-pool-then-gather
    step it replaced, for several steps: live slots' logits and every
    pool row outside the trash page are bit-identical. Slot 1 is idle
    (every entry -1); slot 2 runs from max_len-2 past the end, where both
    route the write to the trash page (or drop it)."""
    import jax.numpy as jnp
    from repro.models import transformer as tf
    cfg, params = model
    pm = np.full((4, MP), -1, np.int32)
    pm[0, :3] = [3, 17, 1]                  # rows 0..23
    pm[2] = np.arange(8, 8 + MP)            # every page: rows 0..63
    pm[3, :2] = [30, 4]                     # rows 0..15
    pos = np.array([20, 0, MAX_LEN - 2, 9], np.int32)
    live = [0, 2, 3]
    caches = _filled_pools(cfg, pm, pos)
    page_map = jnp.asarray(pm)
    new_step = jax.jit(lambda c, t, p: tf.paged_decode_step(
        params, t, c, p, page_map, cfg, RT))
    ref_step = jax.jit(lambda c, t, p: _scatter_then_gather_step(
        params, t, c, p, page_map, cfg, RT))
    new_c, ref_c = caches, caches
    tok = jnp.asarray(np.arange(4, dtype=np.int32)[:, None] + 11)
    for step in range(4):
        p = jnp.asarray(pos + step)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(tf, "_paged_decode_attn", _scatter_then_gather_attn)
            ref_logits, ref_c = ref_step(ref_c, tok, p)
        logits, new_c = new_step(new_c, tok, p)
        np.testing.assert_array_equal(np.asarray(logits)[live],
                                      np.asarray(ref_logits)[live])
        for a, r in zip(jax.tree_util.tree_leaves(new_c),
                        jax.tree_util.tree_leaves(ref_c)):
            np.testing.assert_array_equal(np.asarray(a)[:, :-1],
                                          np.asarray(r)[:, :-1])
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    # the step past the end wrote nothing outside the trash page
    assert (np.asarray(new_c["layers"]["b0"]["pos"])[:, pm[2]] < MAX_LEN
            ).all()


def test_paged_serve_step_updates_pools_in_place(model):
    """The session's compiled paged step aliases every cache input to its
    output and copies no pool, stacked or per layer."""
    import re
    sess = _session(model, slots=4)
    compiled = sess.step_fn.lower(
        sess.params, sess.tokens, sess.caches, sess._put(sess.slot_pos),
        sess._page_map, sess.rng).compile()
    text = compiled.as_text()
    n_cache = len(jax.tree_util.tree_leaves(sess.caches))
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and alias.group(1).count("may-alias") == n_cache
    pages = str(sess.pages + 1)
    pool_copies = [line for line in text.splitlines()
                   if re.search(r"\[([\d,]*)\]\S* copy\(", line)
                   and pages in re.search(r"\[([\d,]*)\]", line)
                   .group(1).split(",")]
    assert pool_copies == []


def test_paged_decode_donates_cache_and_counts_kv_inplace(model):
    """A plain paged decode step consumes the previous cache buffers
    (donated: they report deleted) and its
    ``decode`` event says ``kv_inplace=1``; a speculative step, which
    reads the cache twice, says 0."""
    from repro.runtime.telemetry import Tracer
    cfg, _ = model
    events = []

    class Sink:
        def on_event(self, ev):
            if ev.kind == "decode":
                events.append(ev)

    tracer = Tracer().add_sink(Sink())
    sess = _session(model, slots=2, telemetry=tracer)
    for i, p in enumerate(_prompts(cfg, 2, seed=6)):
        sess.admit(Request(uid=i, prompt=p, max_new=8))
    before = jax.tree_util.tree_leaves(sess.caches)
    sess.join_decode(sess.dispatch_decode())
    assert all(a.is_deleted() for a in before)
    assert [ev.meta["kv_inplace"] for ev in events] == [1]

    spec = _session(model, slots=2, speculative=2, telemetry=tracer)
    spec.admit(Request(uid=9, prompt=_prompts(cfg, 1, seed=7)[0],
                       max_new=8))
    kept = jax.tree_util.tree_leaves(spec.caches)
    spec.join_decode(spec.dispatch_decode())
    assert events[-1].meta["spec_k"] == 2
    assert events[-1].meta["kv_inplace"] == 0
    assert not any(a.is_deleted() for a in kept)


# ---------------------------------------------------------------------------
# Paged flash-decode kernel vs jnp reference (interpret mode)
# ---------------------------------------------------------------------------

def test_paged_kernel_matches_reference():
    import jax.numpy as jnp
    from repro.kernels.paged_attention import (
        paged_attention_reference, paged_flash_decode_pallas)
    B, h, kvh, hd, ps, mp = 3, 4, 2, 16, 8, 4
    pool = B * mp + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, h, hd), jnp.float32)
    k_pages = jax.random.normal(kk, (pool, ps, kvh, hd), jnp.float32)
    v_pages = jax.random.normal(kv, (pool, ps, kvh, hd), jnp.float32)
    pm = np.full((B, mp), -1, np.int32)
    pm[0, :2] = [5, 9]                       # partially-filled table
    pm[1, :4] = [0, 1, 2, 3]                 # full table
    pm[2, :1] = [7]                          # single page, single token
    lengths = jnp.asarray([13, 32, 1], jnp.int32)
    ref = paged_attention_reference(q, k_pages, v_pages, jnp.asarray(pm),
                                    lengths)
    out = paged_flash_decode_pallas(q, k_pages, v_pages, jnp.asarray(pm),
                                    lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_backend_registered():
    import repro.kernels.paged_attention  # noqa: F401
    from repro.kernels.registry import available_backends, get_backend
    assert "pallas_paged" in available_backends()
    assert "paged" in get_backend("pallas_paged").description


def test_pagedsweep_records_feed_autotune_store(tmp_path):
    from repro.core import execution as ex
    from repro.core.autotune import AutotuneStore
    from repro.kernels.paged_attention import sweep_paged_tilings
    recs = sweep_paged_tilings(batch=2, seq=32, head_dim=16,
                               page_sizes=[8, 16], iters=1,
                               record_cache=False)
    assert len(recs) == 2
    m, n, k, prec, blocks = ex.parse_pagedsweep_name(recs[0].name)
    assert (m, n, k, prec) == (2, 32, 16, "bf16") and blocks[1] in (8, 16)
    store = AutotuneStore(str(tmp_path))
    assert store.add_records(recs) == 2
    # both geometries share the (m, k, n, prec) key; the min-latency
    # page size wins the block entry
    entry = store.blocks[(2, 16, 32, "bf16")]
    assert entry[0] in ((1, 8, 16), (1, 16, 16))
