"""Parameter counts of the configuration files, and the roofline and MFU
arithmetic, against sizes worked by hand and against the numbers the
harness computed before Qwen2 moved into ``bench/archs/qwen2.py``."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

from harness import flops, spec  # noqa: E402
from harness.program import Model  # noqa: E402
from harness.session import Call  # noqa: E402

qwen2 = spec.load_arch("qwen2")
Qwen2 = qwen2.Qwen2
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def step(rows, width, ctx_cells, **kw):
    return Call("step", 0.0, 1.0, rows=rows, width=width,
                ctx_cells=ctx_cells, **kw)


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
    assert qwen2.kv_bytes_per_token(m) == 28 * 2 * 2 * 128 * 2


def test_qwen2_7b_layer_embedding_and_head():
    c = config("qwen2-7b-spin-0.5b")
    m = Qwen2.from_hf("t", c)
    # attention 3584 x (28 + 2 x 4) x 128 + 28 x 128 x 3584 + biases,
    # MLP 3 x 3584 x 18944, two norms
    attn = 3584 * 36 * 128 + 36 * 128 + 3584 * 3584
    assert qwen2.layer_params(m) == attn + 3 * 3584 * 18944 + 2 * 3584
    assert qwen2.layer_params(m) == 233_057_792
    whole = Qwen2.from_hf("w", dict(c, num_hidden_layers=28, vocab_size=152064))
    assert whole.params_count() == 7_615_616_512
    assert 2 * 152064 * 3584 == 1_089_994_752
    assert m.layers == 14 and qwen2.kv_bytes_per_token(m) == 28672


def test_verify_of_the_7b_stage_is_bound_by_its_weights():
    m = Qwen2.from_hf("t", config("qwen2-7b-spin-0.5b"))
    # 14 layers + head (3584 x 151936) + final norm, 2 bytes each
    wb = 2 * (14 * 233_057_792 + 3584 * 151936 + 3584)
    assert qwen2.weight_bytes(m) == wb
    f, b = qwen2.verify_work(m, step(1, 4, 0))
    assert b == wb + 28672 * 5 + 2 * 5 * 151936
    assert flops.verify_least_s(Model(qwen2, m), V5E, step(1, 4, 0)) == \
        pytest.approx(b / 819e9)
    # the embedding table is gathered, not read whole: 7.6 GB, 9.3 ms
    assert 9.2e-3 < b / 819e9 < 9.4e-3
    # a full pool: 20 rows of 1000 cached cells each
    f, b = qwen2.verify_work(m, step(20, 4, 20_000))
    q = 20 * 5
    assert f == pytest.approx(2 * (14 * 233_057_792 + 3584 * 151936) * q
                              + 4 * 14 * 28 * 128 * (5 * 20_000 + 20 * 15))
    assert flops.least_s(f, b, V5E) == pytest.approx(b / 819e9)


def test_draft_token_of_the_drafters():
    d = drafter()
    assert qwen2.weight_bytes(d) / 819e9 == pytest.approx(1.2e-3, rel=0.05)
    m = Qwen2.from_hf("t", QWEN2_1_5B)
    assert qwen2.weight_bytes(m) / 819e9 == pytest.approx(3.77e-3, rel=0.01)


def test_prefill_and_token_flops():
    m = Qwen2.from_hf("t", config("qwen2-7b-spin-0.5b"))
    p = qwen2.matmul_params(m)
    assert p == 14 * 233_057_792 + 3584 * 151936
    assert qwen2.token_flops(m, 0) == 2 * p + 4 * 14 * 28 * 128
    # 2048 prompt tokens: 2 x 3.81G x 2048 = 15.6 TFLOP of matmuls
    assert qwen2.prefill_flops(m, 2048) == pytest.approx(
        2 * p * 2048 + 4 * 14 * 28 * 128 * 2048 * 2049 / 2)
    assert 15e12 < qwen2.prefill_flops(m, 2048) < 17e12


# What the harness computed for Qwen2 before its sizes and counts moved
# into bench/archs/qwen2.py: they must not move by one unit in the last
# place, or every reading of verify_roofline and step_mfu would.
PINNED = {
    "qwen2-7b-spin-0.5b": {
        "layer_params": 233057792, "matmul_params": 3807347712,
        "weight_bytes": 7614702592, "kv_bytes_per_token": 28672,
        "token_flops": [7614896128.0, 7615096832.0, 7815399424.0,
                        8237078528.0],
        "prefill_flops": [7614896128.0, 564044414976.0, 16016008544256.0],
        "verify_work": [[38076487680.0, 7616365312],
                        [781600153600.0, 8221396992],
                        [504338114560.0, 7903282944]],
        "verify_least_s": [0.009299591345543345, 0.010038335765567766,
                           0.009649918124542125]},
    "drafter": {
        "layer_params": 14912384, "matmul_params": 494031872,
        "weight_bytes": 988065536, "kv_bytes_per_token": 12288,
        "token_flops": [988149760.0, 988235776.0, 1074079744.0,
                        1254799360.0],
        "prefill_flops": [988149760.0, 73355411456.0, 2204031254528.0],
        "verify_work": [[4941608960.0, 989646336],
                        [107433779200.0, 1265441536],
                        [68245391360.0, 1123029504]],
        "verify_least_s": [0.0012083593846153847, 0.0015451056605616607,
                           0.0013712203956043956]},
}
STEPS = ((1, 4, 0), (20, 4, 20000), (13, 4, 9311))


@pytest.mark.parametrize("which", sorted(PINNED))
def test_qwen2_work_equals_the_numbers_before_the_move(which):
    c = config("qwen2-7b-spin-0.5b")
    m = Qwen2.from_hf("t", c if which != "drafter" else c["drafters"][0])
    want = PINNED[which]
    got = {
        "layer_params": qwen2.layer_params(m),
        "matmul_params": qwen2.matmul_params(m),
        "weight_bytes": qwen2.weight_bytes(m),
        "kv_bytes_per_token": qwen2.kv_bytes_per_token(m),
        "token_flops": [qwen2.token_flops(m, n) for n in (0, 1, 999, 3100)],
        "prefill_flops": [qwen2.prefill_flops(m, n) for n in (1, 74, 2048)],
        "verify_work": [list(qwen2.verify_work(m, step(*s))) for s in STEPS],
        "verify_least_s": [flops.verify_least_s(Model(qwen2, m), V5E,
                                                step(*s)) for s in STEPS],
    }
    assert got == want


def test_qwen2_verify_work_reads_only_rows_width_and_context():
    m = Qwen2.from_hf("t", config("qwen2-7b-spin-0.5b"))
    a = step(13, 4, 9311, tokens=20,
             record={"active": 13, "tokens": 20, "micro_batches": [1, 2]})
    b = Call("add", 7.0, 9.5, rows=13, width=4, ctx_cells=9311, tokens=0,
             record={"experts_touched": 64})
    assert qwen2.verify_work(m, a) == qwen2.verify_work(m, b)
    for other in (step(14, 4, 9311), step(13, 3, 9311), step(13, 4, 9312)):
        assert qwen2.verify_work(m, other) != qwen2.verify_work(m, a)
