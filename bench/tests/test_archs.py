"""Architecture modules: the llama module gives what the harness gave
before modules existed, and a new architecture enters by files alone."""
import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import archs, model, work
from bench import run as R

from .conftest import DATA, make_bench

# What the harness computed before architecture modules (commit 3904110),
# for both configuration files of the benchmark.
S_G = {"a": 0.07220891637579577, "d": 0.02551551815399144,
       "f": 0.04419417382415922}
S_D = {"d": 0.011048543456039806, "f": 0.006739548325214901}
SHAPES = {
    "granite-moe-3b-a800m": {
        "embed": ((49408, 1536), "bfloat16", 0.0013),
        "final_norm": ((1536,), "float32", 0.0),
        "layers/b0/attn/w_k": ((32, 1536, 512), "bfloat16", S_G["a"]),
        "layers/b0/attn/w_o": ((32, 1536, 1536), "bfloat16", S_G["d"]),
        "layers/b0/attn/w_q": ((32, 1536, 1536), "bfloat16", S_G["a"]),
        "layers/b0/attn/w_v": ((32, 1536, 512), "bfloat16", S_G["d"]),
        "layers/b0/moe/router": ((32, 1536, 40), "float32",
                                 0.10206207261596575),
        "layers/b0/moe/w_down": ((32, 40, 512, 1536), "bfloat16",
                                 S_G["f"]),
        "layers/b0/moe/w_gate": ((32, 40, 1536, 512), "bfloat16",
                                 S_G["d"]),
        "layers/b0/moe/w_up": ((32, 40, 1536, 512), "bfloat16", S_G["d"]),
        "layers/b0/norm1": ((32, 1536), "float32", 0.0),
        "layers/b0/norm2": ((32, 1536), "float32", 0.0),
    },
    "deepseek-llm-67b.stage4": {
        "embed": ((102400, 8192), "bfloat16", 1.0),
        "final_norm": ((8192,), "float32", 0.0),
        "head": ((8192, 102400), "bfloat16", S_D["d"]),
        "layers/b0/attn/w_k": ((4, 8192, 1024), "bfloat16", S_D["d"]),
        "layers/b0/attn/w_o": ((4, 8192, 8192), "bfloat16", S_D["d"]),
        "layers/b0/attn/w_q": ((4, 8192, 8192), "bfloat16", S_D["d"]),
        "layers/b0/attn/w_v": ((4, 8192, 1024), "bfloat16", S_D["d"]),
        "layers/b0/mlp/w_down": ((4, 22016, 8192), "bfloat16", S_D["f"]),
        "layers/b0/mlp/w_gate": ((4, 8192, 22016), "bfloat16", S_D["d"]),
        "layers/b0/mlp/w_up": ((4, 8192, 22016), "bfloat16", S_D["d"]),
        "layers/b0/norm1": ((4, 8192), "float32", 0.0),
        "layers/b0/norm2": ((4, 8192), "float32", 0.0),
    },
}
POSITIONS = {
    "granite-moe-3b-a800m": [[0], [127, 1023, 1535],
                             list(range(5, 1536, 64))],
    "deepseek-llm-67b.stage4": [[0], [1023, 2047, 3071, 4095],
                                list(range(17, 4096, 256))],
}
WORK = {
    "granite-moe-3b-a800m": {
        "kv_bytes_per_token": 65536,
        "prefill_flops": {1: 1765745664, 128: 208435946496,
                          1024: 1756624856064, 3072: 5888047850496},
        "decode_flops": [1765745664, 5825129472, 45874372608],
        "decode_bytes": [1769546751.9999998, 3685103738.879999,
                         7739862373.494865],
    },
    "deepseek-llm-67b.stage4": {
        "kv_bytes_per_token": 16384,
        "prefill_flops": {1: 7214333952, 128: 711429455872,
                          1024: 5739821137920, 3072: 17628424830976},
        "decode_flops": [7214333952, 30198988800, 119491526656],
        "decode_bytes": [7214219264.0, 7381975040.0, 7722237952.0],
    },
}
# sha256[:16] of each leaf's bytes, seed 2**31 + 3, on the CPU
TINY_BASE = {
    "embed": "736a585743c40181", "final_norm": "2570d51cf7104a1b",
    "head": "6fe56a891d9ee534",
    "layers/b0/attn/w_k": "97b64bf0a1184480",
    "layers/b0/attn/w_o": "402ea1555c9f70d6",
    "layers/b0/attn/w_q": "87c46c3e124aee18",
    "layers/b0/attn/w_v": "42a2ba4b2284443b",
    "layers/b0/moe/router": "65e11d5761ff98e7",
    "layers/b0/moe/w_down": "89c5211bb45a695e",
    "layers/b0/moe/w_gate": "060a5ca0fb291cff",
    "layers/b0/moe/w_up": "e2d438013fc5ebe8",
    "layers/b0/norm1": "bbd1c48207bca1c6",
    "layers/b0/norm2": "081f6573a2dc66be",
}
GRANITE = {"embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
           "residual_multiplier": 0.22, "logits_scaling": 6.0,
           "tie_word_embeddings": True}
TINY_GRANITE_FOLDED = {
    "embed": "a6826a0cd9b809dd", "final_norm": "2570d51cf7104a1b",
    "head": "159847a98f9ab67a",
    "layers/b0/attn/w_k": "ca2d600bee182130",
    "layers/b0/attn/w_o": "fa0b5a98ae91d8ba",
    "layers/b0/attn/w_q": "478e395a7d1197da",
    "layers/b0/attn/w_v": "00ded49cf6580d0c",
    "layers/b0/moe/router": "2f8d73084f1ef91f",
    "layers/b0/moe/w_down": "ca13cc3f06adb1ed",
    "layers/b0/moe/w_gate": "b94317c102f97b0a",
    "layers/b0/moe/w_up": "060a5ca0fb291cff",
    "layers/b0/norm1": "fb9ec99eb9083893",
    "layers/b0/norm2": "bbd1c48207bca1c6",
}
NAMES = sorted(SHAPES)


@pytest.mark.parametrize("name", NAMES)
def test_llama_module_gives_the_parents_shapes(name):
    conf = model.load_config(name)
    assert archs.name_of(conf) == "llama"
    assert model.weight_shapes(conf) == SHAPES[name]


@pytest.mark.parametrize("name", NAMES)
def test_llama_module_gives_the_parents_work(name):
    conf = model.load_config(name)
    want = WORK[name]
    assert work.kv_bytes_per_token(conf) == want["kv_bytes_per_token"]
    for T, flops in want["prefill_flops"].items():
        assert work.prefill_flops(conf, T) == flops
    for pos, flops, nbytes in zip(POSITIONS[name], want["decode_flops"],
                                  want["decode_bytes"]):
        assert work.decode_flops(conf, pos) == flops
        assert work.decode_bytes(conf, pos) == nbytes


@pytest.mark.parametrize("name", NAMES)
def test_layer_kinds_are_the_programs(name):
    conf = model.load_config(name)
    cfg = model.arch_config(conf)
    kinds = archs.of(conf).layer_kinds(conf)
    assert kinds == cfg.superlayer_pattern * cfg.num_layers
    assert archs.of(conf).state_bytes_per_slot(conf) == 0


def _digests(params) -> dict:
    return {"/".join(k.key for k in path):
            hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
            for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("extra,want", [
    ({}, TINY_BASE), (GRANITE, TINY_GRANITE_FOLDED),
], ids=["moe", "granite-equations"])
def test_weights_are_the_parents_bit_for_bit(extra, want):
    conf = dict(json.loads((DATA / "tiny.json").read_text()), **extra)
    assert _digests(model.init_weights(conf, 2**31 + 3)) == want


def test_new_architecture_enters_by_files_alone(tmp_path):
    """A test-only architecture with a shared expert (module and
    reference in data/) serves correctly through the harness."""
    root, cell = make_bench(
        tmp_path, "tiny-shared.json", "tiny-traffic.json",
        archs=[("moe_shared_arch.py", "moe_shared")],
        references=[("moe_shared_ref.py", "moe_shared")])
    res = R.run_cell(cell, 2**31 + 29, 2.0, True, bench=root,
                     require_chip=False)
    assert res["correct"], res["checks"]
    conf = model.load_config("tiny-shared", root)
    assert model.arch_config(conf).moe_shared_expert
    assert model.init_weights(conf, 1)["layers"]["b0"]["moe"]["shared"][
        "w_down"].shape == (2, 32, 64)
    plain = json.loads((DATA / "tiny.json").read_text())
    shared = 3 * 64 * 32              # the shared MLP's weights, a layer
    assert work.decode_flops(conf, [5, 9]) == \
        work.decode_flops(plain, [5, 9]) + 2 * 2 * shared * 2
    assert work.decode_bytes(conf, [5, 9]) == \
        work.decode_bytes(plain, [5, 9]) + 2 * shared * work.BF16
    assert "decode_step_ms" in res["metrics"]



def test_program_without_the_shared_expert_is_caught(tmp_path, monkeypatch):
    """The test architecture's reference counts: served by a program that
    leaves the shared expert out, the run is not correct."""
    root, cell = make_bench(
        tmp_path, "tiny-shared.json", "tiny-traffic.json",
        archs=[("moe_shared_arch.py", "moe_shared")],
        references=[("moe_shared_ref.py", "moe_shared")])
    load = archs.load

    def without_shared(name, bench):
        mod = load(name, bench)
        full = mod.arch_config
        mod.arch_config = lambda conf: dataclasses.replace(
            full(conf), moe_shared_expert=False)
        return mod

    monkeypatch.setattr(archs, "load", without_shared)
    res = R.run_cell(cell, 2**31 + 29, 2.0, False, bench=root,
                     require_chip=False)
    assert not res["correct"]
    assert res["checks"]["mean_gap"]["value"] > \
        res["checks"]["mean_gap"]["limit"]
