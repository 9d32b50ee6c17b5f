"""Parameter counts of the configuration files, and the roofline and MFU
arithmetic, against sizes worked by hand."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

from harness import flops  # noqa: E402
from harness.weights import Qwen2  # noqa: E402

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def drafter():
    return Qwen2.from_hf("d", config("qwen2-7b-spin-0.5b")["drafters"][0])


def test_qwen2_0_5b_has_494m_parameters():
    # 24 x 14.91M + 151936 x 896 tied (Qwen2 report, Table 1: 0.5B)
    assert drafter().params_count() == 494_032_768


QWEN2_1_5B = {  # Qwen/Qwen2-1.5B config.json
    "hidden_size": 1536, "intermediate_size": 8960,
    "num_attention_heads": 12, "num_key_value_heads": 2,
    "num_hidden_layers": 28, "vocab_size": 151936, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": True}


def test_qwen2_1_5b_has_1_54b_parameters():
    m = Qwen2.from_hf("t", QWEN2_1_5B)
    assert m.params_count() == 1_543_714_304
    assert flops.kv_bytes_per_token(m) == 28 * 2 * 2 * 128 * 2


def test_qwen2_7b_layer_embedding_and_head():
    c = config("qwen2-7b-spin-0.5b")
    m = Qwen2.from_hf("t", c)
    # attention 3584 x (28 + 2 x 4) x 128 + 28 x 128 x 3584 + biases,
    # MLP 3 x 3584 x 18944, two norms
    attn = 3584 * 36 * 128 + 36 * 128 + 3584 * 3584
    assert flops.layer_params(m) == attn + 3 * 3584 * 18944 + 2 * 3584
    assert flops.layer_params(m) == 233_057_792
    whole = Qwen2.from_hf("w", dict(c, num_hidden_layers=28, vocab_size=152064))
    assert whole.params_count() == 7_615_616_512
    assert 2 * 152064 * 3584 == 1_089_994_752
    assert m.layers == 14 and flops.kv_bytes_per_token(m) == 28672


def test_verify_of_the_7b_stage_is_bound_by_its_weights():
    m = Qwen2.from_hf("t", config("qwen2-7b-spin-0.5b"))
    # 14 layers + head (3584 x 151936) + final norm, 2 bytes each
    wb = 2 * (14 * 233_057_792 + 3584 * 151936 + 3584)
    assert flops.weight_bytes(m) == wb
    f, b = flops.verify_work(m, rows=1, width=4, ctx_cells=0,
                             padded_vocab=151936)
    assert b == wb + 28672 * 5 + 2 * 5 * 151936
    assert flops.verify_least_s(m, V5E, 1, 4, 0) == pytest.approx(b / 819e9)
    # the embedding table is gathered, not read whole: 7.6 GB, 9.3 ms
    assert 9.2e-3 < b / 819e9 < 9.4e-3
    # a full pool: 20 rows of 1000 cached cells each
    f, b = flops.verify_work(m, 20, 4, 20_000, 151936)
    q = 20 * 5
    assert f == pytest.approx(2 * (14 * 233_057_792 + 3584 * 151936) * q
                              + 4 * 14 * 28 * 128 * (5 * 20_000 + 20 * 15))
    assert flops.least_s(f, b, V5E) == pytest.approx(b / 819e9)


def test_draft_token_of_the_drafters():
    d = drafter()
    assert flops.weight_bytes(d) / 819e9 == pytest.approx(1.2e-3, rel=0.05)
    m = Qwen2.from_hf("t", QWEN2_1_5B)
    assert flops.weight_bytes(m) / 819e9 == pytest.approx(3.77e-3, rel=0.01)


def test_prefill_and_token_flops():
    m = Qwen2.from_hf("t", config("qwen2-7b-spin-0.5b"))
    p = flops.matmul_params(m)
    assert p == 14 * 233_057_792 + 3584 * 151936
    assert flops.token_flops(m, 0) == 2 * p + 4 * 14 * 28 * 128
    # 2048 prompt tokens: 2 x 3.81G x 2048 = 15.6 TFLOP of matmuls
    assert flops.prefill_flops(m, 2048) == pytest.approx(
        2 * p * 2048 + 4 * 14 * 28 * 128 * 2048 * 2049 / 2)
    assert 15e12 < flops.prefill_flops(m, 2048) < 17e12
