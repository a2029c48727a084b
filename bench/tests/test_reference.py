"""The benchmark's weights fit the program, and its plain reference
computes the program's model: in float32 on the CPU the program on the
folded weights and the reference on the published ones agree."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model
from bench.references import llama_moe

from .conftest import DATA

DENSE = {"name": "tiny-dense", "hidden_size": 64, "intermediate_size": 96,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 1, "vocab_size": 300, "rms_norm_eps": 1e-6,
         "rope_theta": 10000.0, "serving": {}}


GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
           "residual_multiplier": 0.22, "logits_scaling": 6.0,
           "tie_word_embeddings": True}


def _confs():
    moe = json.loads((DATA / "tiny.json").read_text())
    granite = dict(moe, **GRANITE, weights=dict(
        moe["weights"], embed_std=6.0 * moe["hidden_size"] ** -0.5))
    return [moe, DENSE, granite]


IDS = ["moe", "dense", "granite-equations"]


@pytest.mark.parametrize("conf", _confs(), ids=IDS)
def test_weights_have_the_programs_layout(conf):
    from repro.models import params_shape
    want = params_shape(model.arch_config(conf))
    got = model.init_weights(conf, 0)

    def sig(tree):
        return jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    assert sig(got) == sig(want)


@pytest.mark.parametrize("conf", _confs(), ids=IDS)
def test_reference_matches_program_in_float32(conf):
    from repro.core import execution as ex
    from repro.models import forward
    from repro.models.layers import RuntimeCfg
    cfg = model.arch_config(conf)
    params = model.base_weights(conf, 2**31 + 3)
    p32 = model.fold(conf, jax.tree.map(lambda a: a.astype(jnp.float32),
                                        params))
    T = 64
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], T,
                                             dtype=np.int32)
    rt = RuntimeCfg(param_dtype=jnp.float32, act_dtype=jnp.float32)
    pol = ex.parse_policy("bf16:dense:ref")
    with jax.default_matmul_precision("highest"), ex.policy_scope(pol):
        prog, _ = forward(p32, jnp.asarray(toks)[None], cfg, rt)
    prog = np.asarray(prog[0, :, :conf["vocab_size"]])
    rows = np.arange(T, dtype=np.int32)
    ref = np.asarray(llama_moe.make_forward(conf)(params, toks, rows))
    np.testing.assert_allclose(ref, prog, rtol=0, atol=2e-4 * np.abs(ref).max())


def test_fold_is_identity_without_multipliers():
    base = model.base_weights(DENSE, 5)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        model.fold(DENSE, base), base)
    assert all(jax.tree.leaves(same))
