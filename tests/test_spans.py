"""The engine's host spans and request stamps: the ``spin.*`` span tree a
profiler trace of ``SpinEngine.step`` holds, its counters, their absence
with tracing off, and the host-clock admission stamps on a request."""

import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import Request, make_workload
from repro.models import transformer as T
from repro.serving import stats
from repro.serving.engine import EngineConfig, SpinEngine

VOCAB = 256
PHASES = {"spin.schedule", "spin.assign", "spin.draft", "spin.verify",
          "spin.rollback", "spin.catchup", "spin.commit", "spin.precompute"}


@pytest.fixture(scope="module")
def models():
    cfg_llm = registry.reduced_for("llama-7b", d_model=64, n_heads=4,
                                   n_kv_heads=4, vocab_size=VOCAB,
                                   n_layers=2)
    llm = sd.Bundle(cfg_llm, T.init_params(cfg_llm, jax.random.PRNGKey(0)))
    c = registry.reduced_for("llama-68m", d_model=32, n_heads=4,
                             n_kv_heads=4, vocab_size=VOCAB, n_layers=1)
    ssm = sd.Bundle(c, T.init_params(c, jax.random.PRNGKey(1)))
    return llm, [ssm]


def engine(models, capacity=3, **kw):
    llm, ssms = models
    sel = LBSS(SelectorConfig(n_ssms=len(ssms),
                              batch_limits=[capacity] * len(ssms),
                              alpha=4, beta=2, seed=1))
    ecfg = EngineConfig(gamma=3, max_len=128, capacity=capacity,
                        packed_bucket=128, block_size=16, **kw)
    return SpinEngine(llm, ssms, sel, ecfg)


def host_spans(trace_dir):
    """(name, start, end, metadata) of every ``spin.*`` span in the
    trace's host planes, by start."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spin."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def parent(spans, i):
    """The innermost span enclosing span ``i``."""
    name, s, e, _ = spans[i]
    best = None
    for j, (_, s2, e2, _) in enumerate(spans):
        if j != i and s2 <= s and e <= e2 and (best is None
                                               or s2 >= spans[best][1]):
            best = j
    return None if best is None else spans[best][0]


@pytest.fixture(scope="module")
def traced(models, tmp_path_factory):
    eng = engine(models)
    reqs = make_workload("mix", 3, VOCAB, seed=3, scale=0.25)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        eng.add_requests(reqs)
        recs = [eng.step() for _ in range(3)]
    return eng, recs, host_spans(d)


def test_a_traced_step_holds_the_span_tree(traced):
    eng, recs, spans = traced
    names = [s[0] for s in spans]
    assert names.count("spin.step") == 3
    assert PHASES <= set(names)
    for i, (name, _, _, _) in enumerate(spans):
        up = parent(spans, i)
        if name == "spin.step":
            assert up is None
        elif name == "spin.prefill":
            assert up == "spin.admit"
        elif name == "spin.admit":
            assert up == "spin.schedule"
        elif name == "spin.draft":
            assert up in ("spin.step", "spin.draft")
        elif name == "spin.schedule":
            # add_requests schedules outside any step
            assert up in (None, "spin.step")
        else:
            assert up == "spin.step", (name, up)
    # one span per phase and SSM, never one per row: each step holds the
    # phases once (commit twice: the pools' lengths, then the requests)
    steps = [s for s in spans if s[0] == "spin.step"]
    for _, s0, s1, _ in steps:
        inside = [s[0] for s in spans if s0 < s[1] and s[2] <= s1]
        assert inside.count("spin.verify") == 1
        assert inside.count("spin.catchup") == 1
        assert inside.count("spin.commit") == 2
        assert inside.count("spin.draft") == 2


def test_spans_carry_their_counters(traced):
    eng, recs, spans = traced
    steps = [m for n, _, _, m in spans if n == "spin.step"]
    assert [m["rows"] for m in steps] == [r["active"] for r in recs]
    used, held, alloc = eng.kv_cells()
    last = steps[-1]
    assert (last["kv_used"], last["kv_held"], last["kv_alloc"]) == (
        used, held, alloc)
    assert 0 < used <= held <= alloc
    assert last["waiting"] == len(eng.scheduler.waiting)
    admits = {m["rid"]: m for n, _, _, m in spans if n == "spin.admit"}
    prefills = {m["rid"]: m for n, _, _, m in spans if n == "spin.prefill"}
    assert set(admits) == set(prefills) == set(eng.requests)
    for rid, r in eng.requests.items():
        assert admits[rid]["context"] == prefills[rid]["tokens"] == r.prompt_len
    inner = [m for n, _, _, m in spans if n == "spin.draft" and m]
    assert {m["ssm"] for m in inner} == {0}
    assert all(m["width"] == 3 for m in inner)
    assert all(m["width"] == 3 for n, _, _, m in spans if n == "spin.verify")
    assert all("switches" in m for n, _, _, m in spans if n == "spin.assign")


def fresh(n, seed):
    """``n`` requests due at once, each with room for many steps."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i, dataset="t", difficulty=0.5,
                    prompt=rng.integers(0, VOCAB, 10 + i).astype(np.int32),
                    max_new=24, emitted=[]) for i in range(n)]


@pytest.mark.parametrize("kw", [
    {}, {"kv_layout": "dense", "use_packed_verify": False}],
    ids=["paged-packed", "dense-padded"])
def test_a_step_reads_to_the_host_once_per_drafter_and_once_for_verify(
        models, tmp_path, kw):
    """``spin.step``'s ``reads`` is the drafter's candidates plus verify's
    results, at 2 rows as at 8: no read per row."""
    engines = [engine(models, capacity=8, **kw) for _ in range(2)]
    with jax.profiler.trace(str(tmp_path)):
        for eng, n in zip(engines, (2, 8)):
            eng.add_requests(fresh(n, seed=n))
            for _ in range(2):
                assert eng.step()["active"] == n
                assert eng.reads == len(eng.ssms) + 1
    steps = [m for name, _, _, m in host_spans(str(tmp_path))
             if name == "spin.step"]
    assert [m["rows"] for m in steps] == [2, 2, 8, 8]
    assert [m["reads"] for m in steps] == [2] * 4


def test_no_trace_no_counters(models, monkeypatch):
    """With no trace recorded, a span computes nothing: its counters'
    callables are never called."""
    assert not TraceAnnotation.is_enabled()

    def boom():
        raise AssertionError("counters computed with tracing off")

    with stats.span("spin.x", boom) as s:
        stats.annotate(s, boom)
    eng = engine(models)
    monkeypatch.setattr(eng, "kv_cells", boom)
    monkeypatch.setattr(eng, "_admit_meta", lambda r: boom)
    eng.add_requests(make_workload("mix", 2, VOCAB, seed=5, scale=0.25))
    assert eng.step()["active"] == 2


def test_admission_stamps_on_the_host_clock(models):
    eng = engine(models)
    due = time.perf_counter()
    reqs = make_workload("mix", 4, VOCAB, seed=7, scale=0.25)
    eng.add_requests(reqs)
    eng.run(max_slots=200)
    end = time.perf_counter()
    for r in reqs:
        assert due <= r.host_admitted <= r.host_first_token <= end


@pytest.mark.parametrize("chunk", [0, 8])
def test_a_readmitted_request_keeps_its_first_stamps(models, chunk):
    """A request preempted and admitted again keeps the stamps of its
    first admission, whole or chunked prefill alike."""
    eng = engine(models, kv_budget=80, prefill_chunk=chunk,
                 token_budget=24 if chunk else None)
    reqs = make_workload("cp", 4, VOCAB, seed=11, scale=0.35)
    rng = np.random.default_rng(3)
    reqs.append(Request(rid=len(reqs), dataset="long", difficulty=0.5,
                        prompt=rng.integers(0, VOCAB, 24).astype(np.int32),
                        max_new=8, arrival=0.01, emitted=[]))
    admitted, first_token = {}, {}

    def seen():
        for r in reqs:
            if r.host_admitted is not None:
                admitted.setdefault(r.rid, r.host_admitted)
            if r.host_first_token is not None:
                first_token.setdefault(r.rid, r.host_first_token)

    eng.add_requests(reqs)
    seen()
    for _ in range(600):
        rec = eng.step()
        seen()
        if rec.get("done") and not eng.scheduler.outstanding:
            break
    assert any(r.preemptions for r in reqs), "budget never bound"
    for r in reqs:
        assert r.done
        assert r.host_admitted == admitted[r.rid]
        assert r.host_first_token == first_token[r.rid]
        assert r.host_admitted <= r.host_first_token
