"""FLOPs and needed bytes against hand arithmetic at small sizes."""
import pytest

from bench import work

MOE = {"hidden_size": 16, "intermediate_size": 8, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 100,
       "num_local_experts": 4, "num_experts_per_tok": 2}
DENSE = {k: v for k, v in MOE.items()
         if k not in ("num_local_experts", "num_experts_per_tok")}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}


def test_parameter_counts():
    # head_dim 4: q 16x16, k and v 16x8, o 16x16
    assert work.attn_params(MOE) == 256 + 128 + 128 + 256
    assert work.expert_params(MOE) == 3 * 16 * 8
    assert work.router_params(MOE) == 64 and work.router_params(DENSE) == 0
    assert work.active_layer_params(MOE) == 768 + 64 + 2 * 384
    assert work.active_layer_params(DENSE) == 768 + 384
    # K and V, 2 layers, 2 kv heads of 4, bf16
    assert work.kv_bytes_per_token(MOE) == 2 * 2 * 2 * 4 * 2


def test_prefill_flops():
    T = 3
    layers = 2 * (768 + 64 + 768) * 2 * T
    attn = 4 * 4 * 4 * (1 + 2 + 3) * 2
    head = 2 * 16 * 100
    assert work.prefill_flops(MOE, T) == layers + attn + head


def test_expected_distinct_experts():
    # one token routes to exactly k experts; E(1-(1-k/E)^B) otherwise
    assert work.expected_experts(MOE, 1) == pytest.approx(2.0)
    assert work.expected_experts(MOE, 2) == pytest.approx(4 * (1 - 0.25))
    assert work.expected_experts(MOE, 1000) == pytest.approx(4.0)
    assert work.expected_experts(DENSE, 5) == 1.0


def test_decode_work():
    pos = [3, 5]                       # keys seen: 4 and 6
    flops = (2 * 1600 * 2 * 2 + 4 * 4 * 4 * 10 * 2 + 2 * 1600 * 2)
    assert work.decode_flops(MOE, pos) == flops
    experts = 4 * (1 - 0.5 ** 2)
    weights = 768 * 2 + 64 * 4 + experts * 384 * 2
    nbytes = 2 * weights + 1600 * 2 + 64 * 10
    assert work.decode_bytes(MOE, pos) == pytest.approx(nbytes)
    assert work.decode_bytes(MOE, []) == 0.0
    assert work.bound_seconds(flops, nbytes, PEAKS) == \
        pytest.approx(max(flops / 1e3, nbytes / 1e2))
