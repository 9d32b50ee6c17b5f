"""SpinEngine integration: losslessness of the full system (heterogeneous
SSMs + LBSS switching + packed verification), fault tolerance, stragglers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import make_workload
from repro.models import transformer as T
from repro.serving.engine import (EngineConfig, SpinEngine, catchup_inputs,
                                  catchup_rows)

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
    P = len(prompt)
    toks = jnp.asarray(np.asarray(prompt, np.int32))[None]
    lg, cache = llm.prefill(toks, jnp.asarray([P], jnp.int32), P + n_new + 8)
    V = llm.cfg.vocab_size
    tok = jnp.argmax(lg[:, P - 1, :V], -1, keepdims=True).astype(jnp.int32)
    out = [int(tok[0, 0])]
    lengths = jnp.asarray([P], jnp.int32)
    for _ in range(n_new - 1):
        lg2, cache = llm.decode(cache, tok, lengths)
        tok = jnp.argmax(lg2[:, -1, :V], -1, keepdims=True).astype(jnp.int32)
        lengths = lengths + 1
        out.append(int(tok[0, 0]))
    return out


@pytest.mark.parametrize("packed", [True, False])
def test_engine_output_is_lossless(models, packed):
    """The whole system (selector switches, packed verify, pools, rollback)
    must emit exactly the plain-LLM greedy continuation per request."""
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=len(ssms),
                              batch_limits=[6] * len(ssms),
                              alpha=4, beta=2, seed=1))
    ecfg = EngineConfig(gamma=3, max_len=128, capacity=6,
                        use_packed_verify=packed, use_pipeline=True,
                        packed_bucket=128)
    eng = SpinEngine(llm, ssms, sel, ecfg)
    reqs = make_workload("mix", 5, VOCAB, seed=3, scale=0.25)
    eng.add_requests(reqs)
    eng.run(max_slots=80)
    for r in eng.requests.values():
        assert r.done
        want = greedy_reference(llm, r.prompt, r.max_new)
        assert r.emitted[:r.max_new] == want, r.rid


def test_engine_survives_ssm_failure(models):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=len(ssms),
                              batch_limits=[6] * len(ssms),
                              alpha=4, beta=2, seed=2))
    ecfg = EngineConfig(gamma=3, max_len=128, capacity=6,
                        use_packed_verify=False)
    eng = SpinEngine(llm, ssms, sel, ecfg)
    reqs = make_workload("cip", 4, VOCAB, seed=5, scale=0.25)
    eng.add_requests(reqs)
    eng.step()
    eng.fail_ssm(0)                      # kill a replica mid-flight
    eng.run(max_slots=80)
    for r in eng.requests.values():
        assert r.done
        want = greedy_reference(llm, r.prompt, r.max_new)
        assert r.emitted[:r.max_new] == want, r.rid


def test_straggler_mitigation_bounds_makespan(models):
    llm, ssms = models
    def build(mitigate):
        sel = LBSS(SelectorConfig(n_ssms=len(ssms),
                                  batch_limits=[6] * len(ssms),
                                  alpha=4, beta=2, seed=3))
        ecfg = EngineConfig(gamma=3, max_len=128, capacity=4,
                            use_packed_verify=False,
                            straggler_mitigation=mitigate,
                            straggler_factor=1.2)
        return SpinEngine(llm, ssms, sel, ecfg)
    e1 = build(True)
    reqs = make_workload("cp", 4, VOCAB, seed=7, scale=0.25)
    e1.add_requests(reqs)
    e1.run(max_slots=60)
    assert e1.straggler_redispatches > 0
    for r in e1.requests.values():
        assert r.done


def test_catchup_input_gathered_on_device_matches_per_row_reads():
    """The drafters' catch-up input, gathered on the device, is what
    reading verify's results row by row built: drafter and target rows in
    different orders, a drafter row whose request holds no target row, and
    empty drafter rows all read zeros."""
    rng = np.random.default_rng(0)
    N, W, cap = 6, 4, 5
    out_all = jnp.asarray(rng.integers(1, VOCAB, (N, W + 1)), jnp.int32)
    n_acc_all = jnp.asarray(rng.integers(0, W + 1, N), jnp.int32)
    llm_rows = {10: 4, 11: 0, 12: 5, 13: 2}
    ssm_rows = {12: 0, 10: 3, 14: 1, 11: 4}       # 14: no target row
    want_outs = np.zeros((cap, W + 1), np.int32)
    want_nacc = np.zeros(cap, np.int64)
    for rid, row in ssm_rows.items():
        lrow = llm_rows.get(rid)
        if lrow is None:
            continue
        want_outs[row] = np.asarray(out_all[lrow])
        want_nacc[row] = int(n_acc_all[lrow])
    rows = catchup_rows(ssm_rows, llm_rows, cap)
    assert rows.tolist() == [5, -1, -1, 4, 0]
    outs, nacc = catchup_inputs(out_all, n_acc_all, jnp.asarray(rows))
    assert outs.dtype == nacc.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(outs), want_outs)
    np.testing.assert_array_equal(np.asarray(nacc), want_nacc)
    assert want_outs[1].sum() == want_outs[2].sum() == 0
