"""v5e compiles of the main-path Pallas kernels at granite-moe-3b-a800m widths,
and of its whole paged decode step.

Each test compiles for a described (not attached) TPU v5e, so it runs on
the CPU: what Mosaic refuses here, the chip would refuse too. Nothing is
executed and no time is measured. The topology is described inside a
module fixture, never at import, so every xdist worker collects the same
tests and only the worker given this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import flash_attention as fa
from repro.kernels import registry
from repro.models import init_paged_cache, init_params
from repro.models.layers import RuntimeCfg
from repro.runtime.serve_loop import make_paged_serve_step

CFG = get_arch("granite-moe-3b-a800m")
D, FF = CFG.d_model, CFG.d_ff


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable cannot be read back without a chip: keep the
        # persistent cache out of these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the registry's Pallas entries for the chip, not the
    interpreter (the CPU backend would otherwise pick interpret mode)."""
    monkeypatch.setattr(registry, "interpret_mode", lambda: False)
    registry.reset_fallbacks()
    yield
    assert registry.fallback_count() == 0


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (M, K, N): decode rows are the 8 slots, or the per-expert capacity (2,
# padded to 8); prefill rows are a 512-token prompt or an expert's share.
@pytest.mark.parametrize("entry", ["dense", "fp8"])
@pytest.mark.parametrize("m,k,n", [
    (8, D, D),          # decode q/o projection
    (2, D, FF),         # decode expert gate/up, capacity 2 -> padded rows
    (512, D, D),        # prefill projection
    (128, FF, D),       # prefill expert down projection
])
def test_matmul_compiles_for_v5e(one_chip, mosaic, entry, m, k, n):
    fn = registry.get_backend("pallas").entry(entry)
    text = _compile(lambda x, w: fn(x, w), one_chip,
                    ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(8, D), (8, CFG.kv_dim), (512, D)])
@pytest.mark.parametrize("vdtype", [jnp.bfloat16, jnp.float8_e4m3fn])
def test_sparse24_compiles_for_v5e(one_chip, mosaic, m, n, vdtype):
    text = _compile(registry.get_backend("pallas").sparse24, one_chip,
                    ((m, D), jnp.bfloat16), ((D // 2, n), vdtype),
                    ((D // 8, n), jnp.uint8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_compiles_for_v5e(one_chip, causal):
    h, kvh, hd, s = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim, 1024
    text = _compile(
        lambda q, k, v: fa.flash_attention_pallas(q, k, v, causal=causal),
        one_chip, ((1, h, s, hd), jnp.bfloat16),
        ((1, kvh, s, hd), jnp.bfloat16), ((1, kvh, s, hd), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_paged_decode_step_copies_no_pool_on_v5e(one_chip):
    """The whole 32-layer paged decode step at the chat cell's geometry
    (24 slots of 1536 positions, pages of 16), cache donated as the
    session donates it: every cache leaf aliases its output, and the
    only ops that produce a k/v pool, stacked or one layer's, are the
    in-place scatters of the step's new rows: no copy, relayout or
    per-layer slice of a pool. (The int32 pos pool, 4.7 MB, is staged
    for its gather; it is 1/500 of the k/v bytes.)"""
    slots, max_len, page = 24, 1536, 16
    pages = slots * max_len // page
    rt = RuntimeCfg()

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), CFG)))
    caches = shapes(jax.eval_shape(
        lambda: init_paged_cache(CFG, slots, max_len, page, pages)))
    small = shapes({"tokens": jnp.zeros((slots, 1), jnp.int32),
                    "pos": jnp.zeros((slots,), jnp.int32),
                    "page_map": jnp.zeros((slots, max_len // page),
                                          jnp.int32),
                    "rng": jax.eval_shape(lambda: jax.random.PRNGKey(0))})
    step = jax.jit(make_paged_serve_step(CFG, rt), donate_argnums=(2,))
    text = step.lower(params, small["tokens"], caches, small["pos"],
                      small["page_map"], small["rng"]).compile().as_text()
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias and alias.group(1).count("may-alias") == \
        len(jax.tree_util.tree_leaves(caches))
    pool = rf"bf16\[(?:\d+,)?{pages + 1},{page},{CFG.kv_dim}\]"
    ops = re.findall(rf"^\s*(?:ROOT )?%\S+ = {pool}\{{[^}}]*\}} (\S+?)\((.*)$",
                     text, re.M)
    made = [op for op, rest in ops
            if op not in ("parameter", "get-tuple-element", "scatter")
            and not (op == "fusion" and '/scatter"' in rest)]
    assert ops and made == []
