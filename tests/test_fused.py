"""Fused speculative-step kernels (ISSUE 7): interpret-mode validation of
``kernels/fused_verify`` / ``kernels/fused_decode`` against the
``kernels/ref.py`` oracles across tile configs and tree topologies,
autotune-cache behavior (cold-miss fallback, populate/consult roundtrip),
and engine-level bit-identity of ``--fused-kernels on`` vs ``off``."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st
from test_paged import _tree_verify_setup, _verify_setup

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import make_workload
from repro.kernels import autotune, ops, ref
from repro.kernels.fused_decode import fused_paged_decode
from repro.kernels.fused_verify import fused_paged_verify
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, SpinEngine

VOCAB = 256


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------ fused verify ------

@pytest.mark.parametrize("lens,H,Kh,D,bs", [
    ([37, 120, 61], 8, 4, 32, 16),
    ([5, 5], 4, 4, 16, 8),
    ([33, 1, 97, 15], 4, 1, 32, 16),
])
@pytest.mark.parametrize("bq,bk,depth", [
    (128, 0, 1), (8, 0, 2), (16, 8, 3),
])
def test_fused_verify_matches_oracle(lens, H, Kh, D, bs, bq, bk, depth):
    gamma = 4
    nb = sum(max(1, -(-L // bs)) for L in lens) + 2
    args = _verify_setup(lens, bs, nb, H, Kh, D, gamma, seed=3)
    out = fused_paged_verify(*args, bq=bq, bk=bk, depth=depth,
                             interpret=True)
    want = ref.paged_verify_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-2)


@pytest.mark.parametrize("lens,branch_depths,bs", [
    ([37, 61], [[2, 1], [3]], 16),
    ([5, 9], [[1, 1, 1], [4]], 8),
    ([33, 1, 15], [[2, 2], [1, 0], [3]], 8),
])
def test_fused_verify_tree_matches_oracle(lens, branch_depths, bs):
    args = _tree_verify_setup(lens, branch_depths, bs, 4, 2, 16, seed=5)
    out = fused_paged_verify(*args, bq=8, bk=0, depth=2, interpret=True)
    want = ref.paged_verify_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-2)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 3))
def test_fused_verify_tree_property(seed, n):
    """Random request mixes x branch topologies x tile configs: the fused
    inline mask path must track the dense oracle everywhere."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(1, 70)) for _ in range(n)]
    depths = [[int(d) for d in rng.integers(0, 5, rng.integers(1, 4))]
              for _ in range(n)]
    bs = int(rng.choice([8, 16]))
    args = _tree_verify_setup(lens, depths, bs, 4, 2, 16, seed=seed)
    bq = int(rng.choice([8, 32, 128]))
    bk = int(rng.choice([0, bs // 2]))
    depth = int(rng.integers(1, 4))
    out = fused_paged_verify(*args, bq=bq, bk=bk, depth=depth,
                             interpret=True)
    want = ref.paged_verify_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-2)


def test_fused_verify_padding_blocks_never_read():
    """Satellite regression: trailing bucketed-padding entries are clamped
    to the last live fragment (owner -1 keeps them masked), so growing the
    padding tail never changes the output."""
    lens, H, Kh, D, bs = [24, 40], 4, 2, 16, 8
    nb = sum(-(-L // bs) for L in lens) + 2
    q, kp, vp, pseg, ppos, qs, qpos, ids, owner = _verify_setup(
        lens, bs, nb, H, Kh, D, 2, seed=4)
    out1 = fused_paged_verify(q, kp, vp, pseg, ppos, qs, qpos, ids, owner,
                              bq=8, interpret=True)
    pad = 3 * ids.shape[0]                       # much longer padding tail
    ids2 = jnp.concatenate([ids, jnp.zeros(pad, jnp.int32)])
    owner2 = jnp.concatenate([owner, jnp.full(pad, -1, jnp.int32)])
    out2 = fused_paged_verify(q, kp, vp, pseg, ppos, qs, qpos, ids2,
                              owner2, bq=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_paged_verify_trailing_clamp_unchanged():
    """Satellite regression for ``paged_verify_attention``'s new trailing
    clamp (mirroring ``paged_decode_attention``): padding growth is
    output-invariant there too."""
    from repro.kernels.paged_attention import paged_verify_attention
    lens, H, Kh, D, bs = [19, 45, 7], 4, 2, 16, 8
    nb = sum(-(-L // bs) for L in lens) + 3
    q, kp, vp, pseg, ppos, qs, qpos, ids, owner = _verify_setup(
        lens, bs, nb, H, Kh, D, 3, seed=9)
    out1 = paged_verify_attention(q, kp, vp, pseg, ppos, qs, qpos, ids,
                                  owner, bq=8, interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, pseg, ppos, qs, qpos, ids, owner)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(want),
                               atol=2e-5, rtol=1e-2)
    pad = ids.shape[0]
    ids2 = jnp.concatenate([ids, jnp.zeros(pad, jnp.int32)])
    owner2 = jnp.concatenate([owner, jnp.full(pad, -1, jnp.int32)])
    out2 = paged_verify_attention(q, kp, vp, pseg, ppos, qs, qpos, ids2,
                                  owner2, bq=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ------------------------------------------------------ fused decode ------

def _decode_setup(lens, bs, H, Kh, D, Tn, seed=0, idle_rows=0):
    """Rows with fragmented block tables; ``idle_rows`` extra rows own no
    blocks (seg -1 queries, outputs ignored)."""
    rng = np.random.default_rng(seed)
    B = len(lens) + idle_rows
    nbs = [max(1, -(-(L + Tn) // bs)) for L in lens] + [0] * idle_rows
    nb_max = max(nbs)
    N = sum(nbs) + 2
    perm = rng.permutation(N)
    bt = np.full((B, nb_max), -1, np.int32)
    pool_seg = np.full((N, bs), -1, np.int32)
    pool_pos = np.full((N, bs), -1, np.int32)
    m = 0
    for b, L in enumerate(lens):
        for k in range(nbs[b]):
            pb = int(perm[m]); m += 1
            bt[b, k] = pb
            for s in range(bs):
                p = k * bs + s
                if p < L:
                    pool_seg[pb, s] = 0
                    pool_pos[pb, s] = p
    kp = _rand(jax.random.PRNGKey(seed), (N, bs, Kh, D))
    vp = _rand(jax.random.PRNGKey(seed + 1), (N, bs, Kh, D))
    q = _rand(jax.random.PRNGKey(seed + 2), (B, Tn, H, D))
    q_seg = np.zeros((B, Tn), np.int32)
    q_seg[len(lens):] = -1
    q_pos = np.stack([L + np.arange(Tn) for L in lens]
                     + [np.full(Tn, -1)] * idle_rows).astype(np.int32)
    return (q, kp, vp, jnp.asarray(pool_seg), jnp.asarray(pool_pos),
            jnp.asarray(q_seg), jnp.asarray(q_pos), jnp.asarray(bt))


@pytest.mark.parametrize("lens,Tn,bs,bk,depth,idle", [
    ([37, 120, 61], 5, 16, 0, 1, 0),
    ([5, 5], 3, 8, 0, 2, 1),
    ([33, 1, 97, 15], 4, 16, 8, 3, 2),
])
def test_fused_decode_matches_oracle(lens, Tn, bs, bk, depth, idle):
    args = _decode_setup(lens, bs, 4, 2, 16, Tn, seed=7, idle_rows=idle)
    out = fused_paged_decode(*args, bk=bk, depth=depth, interpret=True)
    want = ref.paged_seq_decode_ref(*args)
    live = len(lens)
    np.testing.assert_allclose(np.asarray(out)[:live],
                               np.asarray(want)[:live],
                               atol=2e-5, rtol=1e-2)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_fused_decode_property(seed):
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(1, 80))
            for _ in range(int(rng.integers(1, 4)))]
    bs = int(rng.choice([8, 16]))
    Tn = int(rng.integers(1, 5))
    args = _decode_setup(lens, bs, 4, 2, 16, Tn, seed=seed,
                         idle_rows=int(rng.integers(0, 2)))
    out = fused_paged_decode(*args, bk=int(rng.choice([0, bs // 2])),
                             depth=int(rng.integers(1, 3)), interpret=True)
    want = ref.paged_seq_decode_ref(*args)
    live = len(lens)
    np.testing.assert_allclose(np.asarray(out)[:live],
                               np.asarray(want)[:live],
                               atol=2e-5, rtol=1e-2)


# ------------------------------------------------------ autotune cache ----

def test_autotune_cold_miss_falls_back_to_default(tmp_path):
    path = str(tmp_path / "tune.json")
    autotune.CACHE_STATS.update(hits=0, misses=0)
    cfg = autotune.get_config("verify", H=4, Kh=2, D=16, gamma_max=4,
                              block_size=8, path=path)
    assert cfg == autotune.DEFAULT_CONFIG
    assert autotune.CACHE_STATS["misses"] == 1
    assert autotune.CACHE_STATS["hits"] == 0


def test_autotune_populate_then_consult(tmp_path):
    path = str(tmp_path / "tune.json")
    won = autotune.autotune("decode", H=2, Kh=1, D=8, gamma_max=2,
                            block_size=8, path=path)
    key = autotune.tune_key("decode", H=2, Kh=1, D=8, gamma_max=2,
                            block_size=8)
    cache = autotune.load_cache(path)
    assert key in cache and cache[key]["us"] > 0
    autotune.CACHE_STATS.update(hits=0, misses=0)
    got = autotune.get_config("decode", H=2, Kh=1, D=8, gamma_max=2,
                              block_size=8, path=path)
    assert got == won
    assert autotune.CACHE_STATS["hits"] == 1
    # corrupt cache file degrades to empty (miss), never raises
    with open(path, "w") as f:
        f.write("{not json")
    assert autotune.get_config("decode", H=2, Kh=1, D=8, gamma_max=2,
                               block_size=8,
                               path=path) == autotune.DEFAULT_CONFIG


def test_fused_config_is_jit_cache_key():
    a = autotune.FusedConfig(bq=8, bk=0, depth=2)
    b = autotune.FusedConfig(bq=8, bk=0, depth=2)
    assert a == b and hash(a) == hash(b)
    assert a != autotune.FusedConfig(bq=8, bk=0, depth=1)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Kernels compile on the TPU and interpret on the CPU; any other
    backend is refused instead of silently running interpreted."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=repr(backend)):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret


# ------------------------------------------------- engine bit-identity ----

@pytest.fixture(scope="module")
def models():
    key = jax.random.PRNGKey(0)
    cfg_llm = registry.reduced_for("llama-7b", d_model=96, n_heads=4,
                                   n_kv_heads=4, vocab_size=VOCAB)
    llm = sd.Bundle(cfg_llm, T.init_params(cfg_llm, key))
    ssms = []
    for i, (d, L) in enumerate([(32, 1), (64, 2)]):
        c = registry.reduced_for("llama-68m", d_model=d, n_heads=4,
                                 n_kv_heads=4, vocab_size=VOCAB, n_layers=L)
        ssms.append(sd.Bundle(c, T.init_params(c, jax.random.PRNGKey(i + 1))))
    return llm, ssms


def _run(llm, ssms, **kw):
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[4, 4], alpha=4,
                              beta=2, seed=1))
    defaults = dict(gamma=3, max_len=128, capacity=4, packed_bucket=128,
                    straggler_mitigation=False)
    defaults.update(kw)
    eng = SpinEngine(llm, ssms, sel, EngineConfig(**defaults))
    reqs = make_workload("mix", 4, VOCAB, seed=3, scale=0.2)
    eng.add_requests(reqs)
    eng.run(max_slots=120)
    assert all(r.done for r in eng.requests.values()), "stream must drain"
    return eng


def _same_trace(a, b):
    """Bit-identical output contract AND sim-clock bookkeeping."""
    for rid in a.requests:
        assert a.requests[rid].emitted == b.requests[rid].emitted, rid
    assert a.accepted_tokens == b.accepted_tokens
    assert a.sim_time == b.sim_time, (a.sim_time, b.sim_time)
    sa, sb = a.stats(), b.stats()
    for key in ("drafted", "slots", "goodput_sim", "p95_latency"):
        if key in sa:
            assert sa[key] == sb[key], key


@pytest.mark.parametrize("shape", ["linear", "tree"])
def test_fused_engine_bit_identical(models, shape):
    """``--fused-kernels on`` must emit the same tokens on the same sim
    clock as ``off`` (greedy accept decisions are argmax-stable under the
    kernels' fp reassociation), for linear AND tree speculation."""
    llm, ssms = models
    off = _run(llm, ssms, spec_shape=shape, fused_kernels="off")
    on = _run(llm, ssms, spec_shape=shape, fused_kernels="on")
    assert off.stats()["fused_kernels"] == "off"
    assert on.stats()["fused_kernels"] == "on"
    # the served path runs the default tiles, never the untracked cache
    assert off.stats()["fused_config"] is None
    assert on.stats()["fused_config"] == dataclasses.asdict(
        autotune.DEFAULT_CONFIG)
    _same_trace(off, on)


def test_fused_on_dense_layout_warns_and_falls_back(models):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[4, 4], alpha=4,
                              beta=2, seed=1))
    with pytest.warns(UserWarning, match="fused_kernels"):
        eng = SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=3, max_len=128, capacity=4, kv_layout="dense",
            fused_kernels="on"))
    assert not eng.fused
    assert eng.fused_cfg is None


def test_engine_rejects_unknown_fused_kernels(models):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[4, 4], alpha=4,
                              beta=2, seed=1))
    with pytest.raises(ValueError, match="fused_kernels"):
        SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=3, max_len=128, capacity=4, fused_kernels="sometimes"))


def test_tree_node_budget_error_names_flags(models):
    """Satellite: the config-derived tree budget guard names the flags."""
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[4, 4], alpha=4,
                              beta=2, seed=1))
    with pytest.raises(ValueError) as ei:
        SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=20, spec_shape="tree", spec_branch=16,
            max_len=128, capacity=4))
    msg = str(ei.value)
    assert "--gamma-max" in msg or "gamma_max" in msg
    assert "spec_branch" in msg or "--spec-branch" in msg
    from repro.core import decompose as D
    assert str(D.max_tree_nodes()) in msg
