"""Device idle by phase of the engine's step, from the program's
``spin.*`` spans (``harness/program_spans.py``), and the readers of the
program's admission stamps: on a hand-made trace, on traces without the
spans, and on a traced run on the CPU."""

import importlib.util
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)

import pytest  # noqa: E402

import benchroot  # noqa: E402
from harness import program, program_spans, spec  # noqa: E402
from harness.tracing import Event  # noqa: E402

MS = 1e6  # ns
PHASE_METRICS = ("idle_schedule_ms", "idle_draft_ms", "idle_verify_ms",
                 "idle_commit_ms", "kv_used_share")


def host(name, start, end, **stats):
    return Event(False, "python3", name, start * MS, (end - start) * MS,
                 stats)


def op(start, end):
    return Event(True, "XLA Ops", "fusion", start * MS, (end - start) * MS,
                 {})


def hand_trace(with_steps=True):
    """Two steps.  Step 1 (0-10 ms): schedule 0-1, draft 1-3 with the
    drafter's span 1.5-3 inside it, verify 3-5, rollback 5-5.5, catchup
    5.5-7, commit 7-8, precompute 8-8.5, schedule 8.5-10 with an
    admission 9-10 and its prefill 9.2-9.8.  Device busy 1.6-2.4, 3.5-4.5,
    6-6.5 and 9.3-9.7.  The harness observes 10-11 (idle).  Step 2
    (11-14 ms): verify 11-13 with the device busy 11.5-12.5; 13-14 lies
    in no child span."""
    spin = [
        host("spin.step", 0, 10, rows=3, kv_used=30, kv_alloc=120),
        host("spin.schedule", 0, 1),
        host("spin.draft", 1, 3), host("spin.draft", 1.5, 3, ssm=0, width=4),
        host("spin.verify", 3, 5, width=4), host("spin.rollback", 5, 5.5),
        host("spin.catchup", 5.5, 7), host("spin.commit", 7, 8),
        host("spin.precompute", 8, 8.5), host("spin.schedule", 8.5, 10),
        host("spin.admit", 9, 10, rid=7, context=40),
        host("spin.prefill", 9.2, 9.8, rid=7, tokens=40),
        host("spin.step", 11, 14, rows=3, kv_used=60, kv_alloc=120),
        host("spin.verify", 11, 13, width=4),
    ]
    return [
        host("bench.step", 0, 10), host("bench.observe", 10, 11),
        host("bench.step", 11, 14),
        host("bench.program.draft", 1.6, 1.7),
        op(1.6, 2.4), op(3.5, 4.5), op(6, 6.5), op(9.3, 9.7),
        op(11.5, 12.5),
    ] + (spin if with_steps else [])


def test_idle_goes_to_the_innermost_program_span():
    p = program_spans.reduce_events(hand_trace())
    assert p.steps == 2
    ms = {k: v * 1e3 for k, v in p.idle.items()}
    assert ms[("spin.schedule", "bench.step")] == pytest.approx(1 + 0.5)
    assert ms[("spin.admit", "bench.step")] == pytest.approx(0.4)
    assert ms[("spin.prefill", "bench.step")] == pytest.approx(0.2)
    # 1-1.5 in the phase's span, 1.5-1.6 and 2.4-3 in the drafter's
    assert ms[("spin.draft", "bench.step")] == pytest.approx(0.5 + 0.7)
    assert ms[("spin.verify", "bench.step")] == pytest.approx(1 + 1)
    assert ms[("spin.commit", "bench.step")] == pytest.approx(1)
    assert ms[("spin.step", "bench.step")] == pytest.approx(1)
    assert ms[("", "bench.observe")] == pytest.approx(1)
    assert p.idle_ms("schedule") == pytest.approx(2.1 / 2)
    assert p.idle_ms("draft") == pytest.approx((1.2 + 0.5) / 2)
    assert p.idle_ms("verify") == pytest.approx((2 + 0.5) / 2)
    assert p.idle_ms("commit") == pytest.approx((1 + 1) / 2)
    assert p.kv_used_share == pytest.approx(37.5)


def test_phases_and_the_rest_add_up_to_the_idle():
    p = program_spans.reduce_events(hand_trace())
    busy = 0.8 + 1 + 0.5 + 0.4 + 1
    assert p.idle_s() * 1e3 == pytest.approx(14 - busy)
    phases = sum(p.idle_s(names) for names in program_spans.PHASES.values())
    rest = p.idle_s(("spin.step", ""))
    assert (phases + rest) * 1e3 == pytest.approx(14 - busy)
    inside = p.idle_s(bench="bench.step")
    assert inside * 1e3 == pytest.approx(14 - busy - 1)
    assert rest * 1e3 == pytest.approx(1 + 1)


def test_no_program_spans_or_no_device_no_reduction():
    assert program_spans.reduce_events(hand_trace(False)) is None
    no_device = [e for e in hand_trace() if not e.device]
    assert program_spans.reduce_events(no_device) is None
    assert program_spans.reduce_events([]) is None


class _Run:
    seed = 5


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_readers_find_nothing_without_spin_step(tmp_path, monkeypatch, name):
    path = tmp_path / ".traces" / "cell.5" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(program_spans, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_CACHE", {})
    monkeypatch.setattr(program_spans, "_parse",
                        lambda p: hand_trace(False))
    reader = spec.load_reader(name, os.path.dirname(BENCH))
    assert reader.read(_Run()) is None
    monkeypatch.setattr(program_spans, "_CACHE", {})
    monkeypatch.setattr(program_spans, "_parse", lambda p: hand_trace())
    assert reader.read(_Run()) is not None


def test_readers_find_nothing_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "BENCH_DIR", str(tmp_path))
    for name in PHASE_METRICS:
        reader = spec.load_reader(name, os.path.dirname(BENCH))
        assert reader.read(_Run()) is None


# ------------------------------------------------------ a traced CPU run --

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A ``--trace 1`` session of the tiny cell on the CPU."""
    program.import_program()
    root = benchroot.make_root(tmp_path_factory.mktemp("bench"))
    run_py = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(run_py)
    run_py.loader.exec_module(run)
    import json

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from harness import device
    from harness.session import Session
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    with open(os.path.join(root, "bench", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    device.setup_jax(root)
    peak = spec.peaks_for(spec.load_peaks(root), "cpu")
    seed = 2**33 + 41
    sess = Session(cfg, spec.load_traffic("tiny-mix", root), seed, peak,
                   device.CompileMeter(jax.monitoring), run.PROCESS_START)
    trace_dir = os.path.join(root, "bench", ".traces", f"tiny.mix.{seed}")
    sess.serve(1.5, trace_dir)
    yield root, sess
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_the_program_stamps_admission_after_the_request_was_due(traced):
    _, sess = traced
    stamped = [r for r in sess.requests.values()
               if r.req.host_first_token is not None]
    assert len(stamped) > sess.schedule.resident
    for r in stamped:
        assert r.due <= r.req.host_admitted <= r.req.host_first_token


@pytest.mark.parametrize("name", ["queue_wait_p50_ms", "prefill_ms_p50"])
def test_stamp_readers_read_a_run(traced, name):
    root, sess = traced
    value = spec.load_reader(name, root).read(sess)
    assert value is not None and value >= 0


def test_a_cpu_trace_has_spans_but_no_device(traced, monkeypatch):
    root, sess = traced
    monkeypatch.setattr(program_spans, "BENCH_DIR",
                        os.path.join(root, "bench"))
    path = program_spans.trace_path(sess.seed)
    assert path is not None
    evs = program_spans._parse(path)
    assert any(e.name == "spin.step" for e in evs)
    assert program_spans.phases(sess) is None
    for name in PHASE_METRICS:
        assert spec.load_reader(name, root).read(sess) is None


def test_verify_work_gets_each_traced_steps_call(traced, monkeypatch):
    """``verify_roofline`` hands the target's architecture the ``Call``
    of every traced step, with the record ``SpinEngine.step`` returned."""
    import types
    root, sess = traced
    steps = sess.traced_steps()
    assert steps
    for c in steps:
        assert c.record["active"] == c.rows
        assert c.record["tokens"] == c.tokens
        assert "micro_batches" in c.record
    seen = []

    def verify_work(m, call):
        assert m is sess.target.sizes
        seen.append(call)
        return 1.0, 1.0

    arch = types.SimpleNamespace(verify_work=verify_work)
    monkeypatch.setattr(sess, "target", program.Model(arch, sess.target.sizes))
    monkeypatch.setattr(sess, "trace",
                        types.SimpleNamespace(kind_s={"verify": 1.0}))
    assert spec.load_reader("verify_roofline", root).read(sess) is not None
    assert len(seen) == len(steps)
    assert all(a is b for a, b in zip(seen, steps))
