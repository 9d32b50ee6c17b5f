"""Tree speculation differential suite (ISSUE 6).

The load-bearing guarantees, each pinned by a test:

* ``--spec-shape tree`` with branching 1 is the SAME algorithm as linear
  speculation — bit-identical emitted tokens AND an identical sim clock,
  across paged/dense layouts, adaptive gamma, and chunked prefill;
* branching > 1 stays lossless: every emitted stream equals the plain
  greedy decode of the target model (tree verify accepts the longest
  verified root-to-leaf path, ties to the main chain, bonus = LLM argmax);
* tree mode requires the paged CoW layout — dense falls back to linear
  with a warning and then behaves exactly like linear;
* a drained tree run returns every CoW block to the free list.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import Request, make_workload
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, SpinEngine

VOCAB = 256


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


def greedy_reference(llm, prompt, n_new):
    """Plain greedy decode of the target model — the lossless contract."""
    P = len(prompt)
    toks = jnp.asarray(np.asarray(prompt, np.int32))[None]
    logits, cache = llm.prefill(toks, jnp.asarray([P], jnp.int32),
                                P + n_new + 8)
    V = llm.cfg.vocab_size
    tok = jnp.argmax(logits[:, P - 1, :V], -1, keepdims=True).astype(
        jnp.int32)
    out = [int(tok[0, 0])]
    lengths = jnp.asarray([P], jnp.int32)
    for _ in range(n_new - 1):
        logits, cache = llm.decode(cache, tok, lengths)
        tok = jnp.argmax(logits[:, -1, :V], -1, keepdims=True).astype(
            jnp.int32)
        lengths = lengths + 1
        out.append(int(tok[0, 0]))
    return out


def _run(llm, ssms, **kw):
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[5, 5], alpha=4,
                              beta=2, seed=1))
    defaults = dict(gamma=3, max_len=128, capacity=5, packed_bucket=128,
                    straggler_mitigation=False)
    defaults.update(kw)
    eng = SpinEngine(llm, ssms, sel, EngineConfig(**defaults))
    reqs = make_workload("mix", 5, VOCAB, seed=3, scale=0.25)
    eng.add_requests(reqs)
    eng.run(max_slots=160)
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


# a branching factor of 1 must be THE SAME ALGORITHM as linear drafting,
# not merely lossless: same tokens, same accept counts, same sim clock
CONFIGS = {
    "paged-fixed": dict(),
    "paged-adaptive-chunked": dict(gamma_policy="adaptive", gamma_max=4,
                                   prefill_chunk=8, token_budget=30),
    "paged-kv-budget": dict(kv_budget=512, block_size=16),
    "dense-fallback": dict(kv_layout="dense"),
}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_tree_branch1_bit_identical_to_linear(models, config):
    llm, ssms = models
    kw = CONFIGS[config]
    lin = _run(llm, ssms, **kw)
    with warnings.catch_warnings():
        # dense-fallback: the layout warning is the point of that config
        warnings.simplefilter("ignore")
        tree = _run(llm, ssms, spec_shape="tree", spec_branch=1, **kw)
    _same_trace(lin, tree)


def test_tree_branch2_lossless_and_drains_blocks(models):
    llm, ssms = models
    eng = _run(llm, ssms, spec_shape="tree", spec_branch=2)
    st = eng.stats()
    assert st["spec_shape"] == "tree" and st["spec_branches"] == 2
    assert st["tree_forks"] > 0
    for r in eng.requests.values():
        n = min(r.max_new, len(r.emitted))
        assert list(r.emitted[:n]) == greedy_reference(llm, r.prompt, n), \
            f"request {r.rid} diverged from plain greedy decode"
    # every CoW fork released its references: nothing leaked
    assert eng.llm_pool.free_blocks == eng.llm_pool.num_blocks


def test_a_tree_step_reads_verify_once_at_any_row_count(models):
    """A tree step reads each drafting drafter's candidates and verify's
    results once (adoption and commit share the one copy), at 2 rows as
    at 8."""
    llm, ssms = models
    rng = np.random.default_rng(5)
    for n in (2, 8):
        sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[8, 8], alpha=4,
                                  beta=2, seed=1))
        eng = SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=3, max_len=128, capacity=8, packed_bucket=128,
            spec_shape="tree", spec_branch=2))
        eng.add_requests([
            Request(rid=i, dataset="t", difficulty=0.5,
                    prompt=rng.integers(0, VOCAB, 10 + i).astype(np.int32),
                    max_new=24, emitted=[]) for i in range(n)])
        for _ in range(2):
            assert eng.step()["active"] == n
            drafting = {eng.assignment[rid] for rid in range(n)}
            assert eng.reads == len(drafting) + 1
        assert eng.tree_forks > 0


def test_tree_adaptive_gamma_lossless(models):
    llm, ssms = models
    eng = _run(llm, ssms, spec_shape="tree", spec_branch=2,
               gamma_policy="adaptive", gamma_max=4)
    assert eng.stats()["tree_forks"] > 0
    for r in eng.requests.values():
        n = min(r.max_new, len(r.emitted))
        assert list(r.emitted[:n]) == greedy_reference(llm, r.prompt, n), \
            f"request {r.rid} diverged from plain greedy decode"


def test_tree_on_dense_layout_warns_and_falls_back(models):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[5, 5], alpha=4,
                              beta=2, seed=1))
    with pytest.warns(UserWarning, match="falling back to linear"):
        eng = SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=3, max_len=128, capacity=5, packed_bucket=128,
            straggler_mitigation=False, kv_layout="dense",
            spec_shape="tree", spec_branch=2))
    assert not eng.tree
    assert eng.stats()["spec_shape"] == "linear"
    assert eng.stats()["spec_branches"] == 1


def test_tree_node_budget_guard(models):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=2, batch_limits=[5, 5], alpha=4,
                              beta=2, seed=1))
    with pytest.raises(ValueError, match="32"):
        SpinEngine(llm, ssms, sel, EngineConfig(
            gamma=30, max_len=128, capacity=5, packed_bucket=128,
            spec_shape="tree", spec_branch=4))


def test_serve_cli_rejects_oversized_tree():
    from repro.launch.serve import main
    with pytest.raises(SystemExit):
        main(["--spec-shape", "tree", "--gamma", "30", "--spec-branch", "4"])
    with pytest.raises(SystemExit):
        main(["--spec-shape", "tree", "--spec-branch", "0"])
