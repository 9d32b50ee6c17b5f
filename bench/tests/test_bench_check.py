"""The comparison that decides ``correct``: the seeded weights, the plain
reference against the program's forward, the float8 control, and whole
runs (with the look for a chip skipped) whose timed path is broken
underneath, which must come out not correct."""

import importlib.util
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import benchroot  # noqa: E402
from harness import program, reference, spec, weights  # noqa: E402

program.import_program()
SEED = 2**33 + 17
qwen2 = spec.load_arch("qwen2")
TINY = qwen2.Qwen2("tiny", hidden=64, inter=128, heads=4, kv_heads=2,
                   layers=3, vocab=300, eps=1e-6, theta=1e6, tied=False)


def _run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def _jax_cache_settings():
    """A run turns JAX's persistent compilation cache on in its
    checkout; give the rest of the worker's tests the settings back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    program.import_program()
    return benchroot.make_root(tmp_path_factory.mktemp("bench"))


def test_weights_are_exact_and_regenerate_layer_by_layer():
    import jax
    import jax.numpy as jnp
    key = weights.model_key(SEED, 0)
    tree = jax.jit(lambda k: qwen2.program_params(TINY, k, 512))(key)
    for layer in range(TINY.layers):
        w = qwen2.layer_weights(TINY, key, layer)
        got = tree["scan"]["u0_attn"]
        assert jnp.array_equal(got["w_gate"][layer], w["w_gate"])
        assert jnp.array_equal(got["wq"][layer].reshape(64, -1), w["wq"])
        assert jnp.array_equal(got["ln1"][layer], w["ln1"])
        assert jnp.array_equal(
            w["w_up"].astype(jnp.float32).astype(jnp.bfloat16), w["w_up"])
        assert float(jnp.abs(w["bq"]).max()) > 0
    g = qwen2.global_weights(TINY, key)
    assert tree["embed"].shape == (512, 64)
    assert jnp.array_equal(tree["embed"][:300], g["embed"])
    assert not jnp.any(tree["embed"][300:])
    other = qwen2.global_weights(TINY, weights.model_key(SEED + 2**32, 0))
    assert not jnp.array_equal(other["embed"], g["embed"])


@pytest.mark.parametrize("tied", [False, True])
def test_reference_agrees_with_the_programs_forward(tied):
    """The program in float32 and the reference pick the same token at
    every position; a token the program did not pick reads a gap."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    m = program.Model(qwen2, dataclasses.replace(TINY, tied=tied))
    b = program.make_bundle(m, SEED, 0, dtype="float32")
    toks = np.random.default_rng(0).integers(0, 300, (2, 512)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = T.apply(b.params, b.cfg, tokens=jnp.asarray(toks))
    pick = np.asarray(jnp.argmax(logits[..., :300], -1))
    nxt = np.full((2, 512), -1, np.int32)
    nxt[:, 10:500] = pick[:, 10:500]
    key = weights.model_key(SEED, 0)
    gaps, = reference.compiled_gaps(m, False)(key, toks, nxt)
    assert float(jnp.max(gaps)) < 1e-4
    nxt[nxt >= 0] = (nxt[nxt >= 0] + 1) % 300
    gaps, = reference.compiled_gaps(m, False)(key, toks, nxt)
    assert float(np.min(np.asarray(gaps)[nxt >= 0])) > 1e-3


# Digests of what the harness made for Qwen2 before its weights and
# reference moved into bench/archs/qwen2.py (sha256 of every leaf's path,
# shape, dtype and bytes; of the gaps' float32 bytes).  Equal digests
# mean the same weights on the chip and the same reference readings.
PINNED_WEIGHTS = {
    "tiny.target": "1f1208bcc333c5c0bfe60f2797a5fc2a"
                   "320725965aaf5eb197515a0ac81e0548",
    "tiny.drafter": "9613808f1ed906f7271e3e17275018e5"
                    "ec54b78f76fc391243a51a907232f62e",
    "7b.layer0": "328ad2f6ae6ba24f62a2b9a3f98890f2"
                 "abde47ad1d72529e979e8cc841b16334",
}
PINNED_GAPS = {  # tied: (gaps, control's gaps)
    False: ("f2b169cedad747997015a554ba627b0a"
            "80fcb06865e1acf139611f5d84004b9c",
            "31f9f4227508378d802346b4cbcd18e6"
            "73c7dc8ee6f2ddd67b15230fa275678f"),
    True: ("c0409e7a24f0eac76aa4f8c7f94c0a21"
           "13facda178064c78a83d53af88f09b92",
           "9915baebb18e3cde242f1f66c65d394f"
           "4c017e03e4cbbd41cc86435c312f015f"),
}


def _digest(tree):
    import hashlib

    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode() + str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("which", sorted(PINNED_WEIGHTS))
def test_qwen2_weights_equal_the_ones_before_the_move(which):
    """The program's tree for the tiny configuration's models, and the
    7B configuration's first layer (its vocabulary cut, which no layer
    leaf depends on), bit for bit as before."""
    import dataclasses
    import json

    import jax
    if which == "7b.layer0":
        with open(os.path.join(BENCH, "configs",
                               "qwen2-7b-spin-0.5b.json")) as f:
            m = qwen2.sizes("t", json.load(f))
        m = dataclasses.replace(m, layers=1, vocab=512)
        tree = jax.jit(lambda k: qwen2.program_params(m, k, 512))(
            weights.model_key(SEED, 0))["scan"]
    else:
        with open(os.path.join(TESTS, "data", "tiny.json")) as f:
            target, drafters = program.models(json.load(f))
        i = 0 if which == "tiny.target" else 1
        m = [target, *drafters][i]
        pv = m.arch.model_config(m.sizes).padded_vocab
        tree = jax.jit(lambda k: m.arch.program_params(m.sizes, k, pv))(
            weights.model_key(SEED, i))
    assert _digest(tree) == PINNED_WEIGHTS[which]


@pytest.mark.parametrize("tied", [False, True])
def test_qwen2_reference_equals_the_one_before_the_move(tied):
    """The reference's gaps and its float8 control's, on fixed tokens,
    bit for bit as before."""
    import dataclasses
    import hashlib
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 300, (2, 512)).astype(np.int32)
    nxt = rng.integers(0, 300, (2, 512)).astype(np.int32)
    nxt[:, :10] = -1
    nxt[1, 400:] = -1
    m = program.Model(qwen2, dataclasses.replace(TINY, tied=tied))
    key = weights.model_key(SEED, 0)
    got = [np.asarray(g) for g in (
        reference.compiled_gaps(m, False)(key, toks, nxt)
        + reference.compiled_gaps(m, True)(key, toks, nxt))]
    digests = [hashlib.sha256(g.tobytes()).hexdigest() for g in got]
    want = PINNED_GAPS[tied]
    assert digests == [want[0], want[0], want[1]]


@pytest.fixture(scope="module")
def sound(root):
    return _run_module().run_cell(root, "tiny.mix", SEED, 2.0, 0,
                                  require_chip=False)


def test_a_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["checks"]["requests_finished"]["value"] >= 1
    assert list(sound)[-1] == "checks"
    assert sound["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_float8_control_is_not_correct(root, seed):
    """The reference in float8 put in the program's place: the gap of
    the tokens it would have served passes the tiny cell's limit, where
    the program's own tokens stay under it."""
    import json
    run = _run_module()
    from harness import device, spec
    from harness.session import Session
    with open(os.path.join(root, "bench", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    jax = device.setup_jax(root)
    peak = spec.peaks_for(spec.load_peaks(root), "cpu")
    sess = Session(cfg, spec.load_traffic("tiny-mix", root), seed, peak,
                   device.CompileMeter(jax.monitoring), run.PROCESS_START)
    sess.serve(1.0)
    _, mine, control = sess.verify(control=True)
    limit = cfg["check"]["max_logit_gap"]
    assert max(mine) <= limit < max(control)


def _alter_tokens(engine):
    verify = engine._verify

    def altered(ids, drafts, depths):
        n_acc, out, out_len = verify(ids, drafts, depths)
        return n_acc, (out + 1) % engine.llm.cfg.vocab_size, out_len
    engine._verify = altered


def _state_unchanged(engine):
    engine.step = lambda: {"tokens": 0, "active": 0}


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = _run_module().run_cell(root, "tiny.mix", SEED, 2.0, 0,
                                 require_chip=False, engine_patch=fault)
    assert res["correct"] is False
