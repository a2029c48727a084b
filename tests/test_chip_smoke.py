"""chip_smoke.py on the CPU, and the no-hidden-fallback seams it relies on.

The chip run itself happens on a TPU; here the reduced configuration
rehearses the same main path in interpret mode, and the script is held to
its contract: a JSON last line naming the device, and a non-zero exit
without a TPU unless ``--reduced`` is given.
"""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import concurrency as cc
from repro.core import sparsity as sp
from repro.kernels import fp8_matmul as fm
from repro.kernels import registry
from repro.kernels import sparse24_matmul as sm
from repro.launch import compile_cache

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture
def smoke(monkeypatch):
    # leave JAX's compile cache alone: with the variable set the helper
    # sets nothing, and JAX read its own config before the test began
    monkeypatch.setenv(compile_cache.ENV, "unused-by-this-test")
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)
    spec.loader.exec_module(mod)
    return mod


def test_reduced_smoke_prints_device_json_last(smoke, capsys):
    assert smoke.main(["--reduced"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    dev = jax.devices()[0]
    assert last["device"] == {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())}
    assert any("16/16 requests" in line for line in lines)
    assert any("0 jnp fallbacks" in line for line in lines)


def test_full_smoke_refuses_a_non_tpu_platform(smoke, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_helper_defers_to_the_environment(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV)
    try:
        path = compile_cache.setup_compile_cache()
        assert path == str(SCRIPT.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("entry", ["dense", "fp8", "sparse24"])
def test_pallas_entries_pad_rows_and_run_the_kernel(monkeypatch, entry):
    """M=4 (four decode slots) is padded to the kernel's 8-row tile
    instead of falling back to jnp."""
    calls = []

    def spy(real):
        def wrapped(x, *a, **kw):
            calls.append(x.shape[0])
            return real(x, *a, **kw)
        return wrapped
    monkeypatch.setattr(fm, "fp8_matmul_pallas", spy(fm.fp8_matmul_pallas))
    monkeypatch.setattr(sm, "sparse24_matmul_pallas",
                        spy(sm.sparse24_matmul_pallas))
    registry.reset_fallbacks()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)).astype(jnp.bfloat16)
    be, ref = registry.get_backend("pallas"), registry.get_backend("ref")
    if entry == "sparse24":
        vals, meta = sp.pack_24(sp.prune_24(w))
        got = be.sparse24(x, vals, meta, out_dtype=jnp.float32)
        want = ref.sparse24(x, vals, meta, out_dtype=jnp.float32)
    else:
        got = be.entry(entry)(x, w, out_dtype=jnp.float32)
        want = ref.entry(entry)(x, w, out_dtype=jnp.float32)
    assert calls == [8]
    assert got.shape == (4, 128)
    assert registry.fallback_count() == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_untileable_pallas_shape_warns_once_and_counts():
    registry.reset_fallbacks()
    x = jnp.ones((8, 20), jnp.bfloat16)          # K=20 cannot tile
    w = jnp.ones((20, 8), jnp.bfloat16)
    dense = registry.get_backend("pallas").dense
    with pytest.warns(RuntimeWarning, match="cannot tile"):
        dense(x, w)
    dense(x, w)
    assert registry.fallback_count() == 2
    registry.reset_fallbacks()


class _FakeDevice:
    def __init__(self, kind, platform="tpu"):
        self.platform, self.device_kind = platform, kind


def test_detect_core_count_reads_the_tpu_table(monkeypatch):
    monkeypatch.delenv("REPRO_N_CORES", raising=False)
    monkeypatch.setattr(cc.jax, "devices",
                        lambda: [_FakeDevice("TPU v5 lite")] * 4)
    assert cc.detect_core_count() == 4 * cc.TPU_MXUS["TPU v5 lite"]


def test_detect_core_count_raises_for_an_unknown_accelerator(monkeypatch):
    monkeypatch.delenv("REPRO_N_CORES", raising=False)
    monkeypatch.setattr(cc.jax, "devices", lambda: [_FakeDevice("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        cc.detect_core_count()
