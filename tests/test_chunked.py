"""Chunked prefill + token-budget step planner (ISSUE 3 acceptance).

Covers:
* planner policy — budget split between decode slots and prompt chunks,
  idle-slot progress rule, prefilling lifecycle transitions (no models);
* priority-aware scheduling — admission rank (priority, arrival, rid),
  preemption victims lowest-priority-first, default priority preserves
  FIFO behaviour exactly;
* chunked-vs-monolithic parity — same prompts, same seeds, bit-identical
  emitted tokens, for both paged and dense layouts (acceptance bar);
* mixed slots stay greedy-exact under preemption pressure;
* the chunk query shape maps onto the paged verify kernel (no dedicated
  chunk-prefill kernel) — kernel vs oracle on chunk-over-prefix queries;
* fast-switch precompute with bucketed (O(context)) widths falls back to
  a miss when the context outgrows the precomputed grid.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.core.switching import SwitchManager
from repro.data.workloads import Request, make_workload
from repro.kernels import ref
from repro.kernels.paged_attention import paged_verify_attention
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, SpinEngine
from repro.serving.scheduler import ContinuousScheduler, SchedulerConfig

VOCAB = 256


def _req(rid, arrival=0.0, prompt_len=8, max_new=8, priority=0,
         emitted=None):
    return Request(rid=rid, dataset="cip", difficulty=0.5,
                   prompt=np.zeros(prompt_len, np.int32), max_new=max_new,
                   arrival=arrival, priority=priority,
                   emitted=list(emitted or []))


# ------------------------------------------------------ planner (no jax) --

def test_chunk_grants_follow_admission_and_budget():
    s = ContinuousScheduler(SchedulerConfig(
        capacity=2, max_len=128, gamma=3, prefill_chunk=8, token_budget=24))
    s.submit([_req(0, prompt_len=20), _req(1, prompt_len=20)])
    dec = s.plan(0.0)
    assert [r.rid for r in dec.admit] == [0, 1]
    # nothing is decode-active yet: full budget goes to chunks, 8 each
    assert [(r.rid, n) for r, n in dec.prefill] == [(0, 8), (1, 8)]
    for r in dec.admit:
        s.mark_admitted(r, 0.0)
    assert set(s.prefilling) == {0, 1}
    for r, n in dec.prefill:
        r.prefill_pos += n
    # next slot: both still prefilling, budget 24 covers 8 + 8
    dec = s.plan(1.0)
    assert [(r.rid, n) for r, n in dec.prefill] == [(0, 8), (1, 8)]
    for r, n in dec.prefill:
        r.prefill_pos += n
    # final chunks are the 4-token remainders
    dec = s.plan(2.0)
    assert [(r.rid, n) for r, n in dec.prefill] == [(0, 4), (1, 4)]
    for r, n in dec.prefill:
        r.prefill_pos += n
        s.mark_prefill_done(r)
    assert not s.prefilling
    assert s.plan(3.0).empty


def test_decode_slots_outrank_prefill_in_the_token_budget():
    # budget 12, gamma 3: a decode-active request costs gamma+1 = 4
    # tokens off the top; only the remainder goes to prompt chunks
    s = ContinuousScheduler(SchedulerConfig(
        capacity=3, max_len=128, gamma=3, prefill_chunk=8, token_budget=12))
    a, b = _req(0, prompt_len=8), _req(1, arrival=0.1, prompt_len=8)
    c = _req(2, arrival=0.2, prompt_len=30)
    s.submit([a, b, c])

    def apply(dec, now):
        for r in dec.admit:
            s.mark_admitted(r, now)
        for r, n in dec.prefill:
            r.prefill_pos += n
            if r.prefill_pos >= s.prefill_target(r):
                s.mark_prefill_done(r)

    dec = s.plan(0.1)               # a + b admitted, c not arrived yet
    assert [r.rid for r in dec.admit] == [0, 1]
    # nothing decode-active: a gets a full 8-token chunk (done), b the
    # remaining 4 of the budget
    assert [(r.rid, n) for r, n in dec.prefill] == [(0, 8), (1, 4)]
    apply(dec, 0.1)
    assert 0 not in s.prefilling and 1 in s.prefilling
    dec = s.plan(0.15)              # a decode-active now: 12 - 4 = 8 left
    assert [(r.rid, n) for r, n in dec.prefill] == [(1, 4)]
    apply(dec, 0.15)
    dec = s.plan(0.25)              # c admitted; a + b decode-active
    grants = {r.rid: n for r, n in dec.prefill}
    assert grants == {2: 4}, grants   # 12 - 2*(3+1) = 4 tokens left
    apply(dec, 0.25)
    # idle-slot progress rule: even a zero-leftover budget grants the
    # top-ranked prefiller when nothing is decode-active
    s2 = ContinuousScheduler(SchedulerConfig(
        capacity=1, max_len=128, gamma=3, prefill_chunk=8, token_budget=2))
    s2.submit([_req(5, prompt_len=20)])
    dec2 = s2.plan(0.0)
    assert [(r.rid, n) for r, n in dec2.prefill] == [(5, 2)]
    s2.mark_admitted(dec2.admit[0], 0.0)
    dec2.admit[0].prefill_pos = 2
    dec3 = s2.plan(1.0)
    assert [(r.rid, n) for r, n in dec3.prefill] == [(5, 2)]


def test_preempted_prefilling_request_restarts_from_chunk_zero():
    s = ContinuousScheduler(SchedulerConfig(
        capacity=2, max_len=64, gamma=3, kv_budget=48, prefill_chunk=8))
    a = _req(0, arrival=0.0, prompt_len=10)
    b = _req(1, arrival=1.0, prompt_len=30)
    s.submit([a, b])
    dec = s.plan(1.0)
    for r in dec.admit:
        s.mark_admitted(r, 1.0)
    b.prefill_pos = 8               # b mid-prefill
    a.emitted = list(range(20))     # a outgrows the budget
    s.mark_prefill_done(a)
    dec = s.plan(2.0)
    assert [r.rid for r in dec.preempt] == [1]
    s.mark_preempted(b, 2.0)
    assert b.prefill_pos == 0       # partial KV discarded with the blocks
    assert 1 not in s.prefilling and [r.rid for r in s.waiting] == [1]


# ------------------------------------------------------------- priority --

def test_priority_outranks_arrival_for_admission():
    s = ContinuousScheduler(SchedulerConfig(capacity=2, max_len=64, gamma=3))
    s.submit([_req(0, arrival=0.0, priority=5),
              _req(1, arrival=1.0, priority=0),
              _req(2, arrival=2.0, priority=0)])
    dec = s.plan(2.0)
    assert [r.rid for r in dec.admit] == [1, 2]   # lower value = urgent
    for r in dec.admit:
        s.mark_admitted(r, 2.0)
    assert [r.rid for r in s.waiting] == [0]


def test_preemption_victims_lowest_priority_first_then_latest_arrival():
    s = ContinuousScheduler(SchedulerConfig(capacity=3, max_len=64, gamma=3,
                                            kv_budget=100))
    a = _req(0, arrival=0.0, priority=0, prompt_len=20)
    b = _req(1, arrival=1.0, priority=3, prompt_len=20)
    c = _req(2, arrival=2.0, priority=3, prompt_len=20)
    s.submit([a, b, c])
    dec = s.plan(2.0)
    for r in dec.admit:
        s.mark_admitted(r, 2.0)
    for r in (a, b, c):
        r.emitted = list(range(40))   # 3 * 63 cells > 100 budget
    dec = s.plan(3.0)
    # both class-3 requests go, latest arrival first; the class-0 request
    # keeps its row even though it arrived earliest
    assert [r.rid for r in dec.preempt] == [2, 1]
    assert a.rid not in {r.rid for r in dec.preempt}


def test_default_priority_preserves_fifo_exactly():
    def run(prio_field):
        s = ContinuousScheduler(SchedulerConfig(capacity=2, max_len=64,
                                                gamma=3, kv_budget=40))
        reqs = [_req(i, arrival=0.5 * i, **prio_field) for i in range(4)]
        s.submit(reqs)
        order = []
        for t in (0.0, 0.5, 1.0, 1.5, 2.0):
            dec = s.plan(t)
            for r in dec.admit:
                s.mark_admitted(r, t)
                order.append(r.rid)
            for rid in list(s.running):
                s.mark_finished(rid)
        return order

    assert run({}) == run({"priority": 0}) == [0, 1, 2, 3]


# --------------------------------------------------------- engine parity --

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


def _run_engine(llm, ssms, layout, prefill_chunk, *, token_budget=None,
                kv_budget=None, capacity=4, reqs=None, max_slots=400,
                slots=None):
    """Serve ``reqs`` to the end; ``slots``, a list, collects the
    records ``step()`` returns."""
    sel = LBSS(SelectorConfig(n_ssms=len(ssms),
                              batch_limits=[capacity] * len(ssms),
                              alpha=4, beta=2, seed=1))
    ecfg = EngineConfig(gamma=3, max_len=128, capacity=capacity,
                        use_packed_verify=True, packed_bucket=128,
                        straggler_mitigation=False, kv_layout=layout,
                        block_size=16, kv_budget=kv_budget,
                        prefill_chunk=prefill_chunk,
                        token_budget=token_budget)
    eng = SpinEngine(llm, ssms, sel, ecfg)
    if reqs is None:
        reqs = make_workload("mix", 4, VOCAB, seed=7, scale=0.25,
                             arrival_rate=400.0)
    eng.add_requests(reqs)
    for _ in range(max_slots):
        rec = eng.step()
        if slots is not None:
            slots.append(rec)
        if rec.get("done") and not eng.scheduler.outstanding:
            break
    assert all(r.done for r in eng.requests.values())
    return eng


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_chunked_prefill_bit_identical_to_monolithic(models, layout):
    """Acceptance: same prompts, same seeds -> bit-identical emitted
    tokens whether the prompt is ingested monolithically or in 8-token
    chunks, on both KV layouts."""
    llm, ssms = models
    mono = _run_engine(llm, ssms, layout, 0)
    chunked = _run_engine(llm, ssms, layout, 8, token_budget=48)
    assert chunked.chunked and not mono.chunked
    assert chunked.scheduler.prefill_grants > 0
    for rid in mono.requests:
        assert mono.requests[rid].emitted == chunked.requests[rid].emitted, \
            rid
    if layout == "paged":
        assert chunked.llm_pool.free_blocks == chunked.llm_pool.num_blocks


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


def test_mixed_slots_stay_greedy_exact_under_preemption(models):
    """A long prompt chunk-prefills while short requests decode and the
    KV budget preempts mid-stream: every request must still emit exactly
    the plain greedy continuation."""
    llm, ssms = models
    reqs = make_workload("cp", 4, VOCAB, seed=11, scale=0.35)
    rng = np.random.default_rng(3)
    reqs.append(Request(rid=len(reqs), dataset="long", difficulty=0.5,
                        prompt=rng.integers(0, VOCAB, 24).astype(np.int32),
                        max_new=8, arrival=0.01, emitted=[]))
    slots = []
    eng = _run_engine(llm, ssms, "paged", 8, token_budget=24, kv_budget=80,
                      capacity=3, reqs=reqs, max_slots=600, slots=slots)
    assert eng.scheduler.preemptions > 0, "budget never bound: tune test"
    assert eng.scheduler.prefill_grants > 0
    mixed = sum(1 for rec in slots
                if rec.get("prefill_tokens") and rec.get("active"))
    assert mixed > 0, "no slot ran chunk-prefill and decode together"
    for r in eng.requests.values():
        want = greedy_reference(llm, r.prompt, r.max_new)
        assert r.emitted[:r.max_new] == want, r.rid


def test_chunked_falls_back_to_monolithic_for_recurrent_llm():
    cfg = registry.reduced_for("zamba2-1.2b", d_model=32, n_heads=4,
                               n_kv_heads=4, vocab_size=64, n_layers=2)
    llm = sd.Bundle(cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
    sel = LBSS(SelectorConfig(n_ssms=1, batch_limits=[2], alpha=4, beta=2,
                              seed=1))
    eng = SpinEngine(llm, [llm], sel,
                     EngineConfig(gamma=2, max_len=64, capacity=2,
                                  prefill_chunk=8))
    assert not eng.chunked
    assert eng.scheduler.cfg.prefill_chunk == 0


# --------------------------------------------- kernel shape reuse (chunk) --

def test_chunk_queries_map_onto_paged_verify_kernel():
    """A prompt chunk is queries at positions pos..pos+n-1 over the row's
    blocks — the packed-verify kernel shape with the chunk as the query
    segment.  Kernel (interpret mode) vs oracle on that exact layout."""
    H, Kh, D, bs = 4, 2, 32, 16
    prefix, chunk = 40, 24
    total = prefix + chunk
    nb = -(-total // bs)
    rng = np.random.default_rng(0)
    perm = rng.permutation(nb + 2)          # fragmented block table
    blocks = perm[:nb]
    num_blocks = nb + 2
    pool_seg = np.full((num_blocks, bs), -1, np.int32)
    pool_pos = np.full((num_blocks, bs), -1, np.int32)
    for k, pb in enumerate(blocks):
        for s_ in range(bs):
            p = k * bs + s_
            if p < total:                   # chunk KV already written
                pool_seg[pb, s_] = 0
                pool_pos[pb, s_] = p
    q_pos = (prefix + np.arange(chunk)).astype(np.int32)
    q_seg = np.zeros(chunk, np.int32)
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (chunk, H, D), jnp.float32)
    kp = jax.random.normal(k2, (num_blocks, bs, Kh, D), jnp.float32)
    vp = jax.random.normal(k3, (num_blocks, bs, Kh, D), jnp.float32)
    ids = np.concatenate([blocks, [0]]).astype(np.int32)
    owner = np.concatenate([np.zeros(nb), [-1]]).astype(np.int32)
    args = (q, kp, vp, jnp.asarray(pool_seg), jnp.asarray(pool_pos),
            jnp.asarray(q_seg), jnp.asarray(q_pos), jnp.asarray(ids),
            jnp.asarray(owner))
    out = paged_verify_attention(*args, bq=8, interpret=True)
    want = ref.paged_verify_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-2)


# --------------------------------------------- switch precompute widths --

def test_switch_precompute_bucketed_width_falls_back_on_outgrown_context():
    cfg = registry.reduced_for("llama-68m", d_model=32, n_heads=4,
                               n_kv_heads=4, vocab_size=64, n_layers=1)
    b = sd.Bundle(cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
    sw = SwitchManager([b])
    tokens = np.arange(40) % 64
    # precompute at a bucketed width that covers 24 tokens only
    sw.precompute(7, 0, tokens, 16, 24)
    assert sw.pre[7].width == 24
    # context grew past the precomputed grid: must be a miss (a hit would
    # silently drop catch-up KV writes past the 24-slot cache)
    cache, recomputed = sw.switch(7, 0, tokens, 40, 48)
    assert sw.misses == 1 and sw.hits == 0
    assert recomputed == 40
    # within the width: normal hit with delta catch-up
    sw.precompute(8, 0, tokens, 16, 48)
    cache, recomputed = sw.switch(8, 0, tokens, 20, 48)
    assert sw.hits == 1
    assert recomputed == 4
