"""The trace reduction: device busy union, device time per kind of
program, and idle time attributed to the harness span open over it; on
a hand-made trace and on one engine step recorded on a v5e chip."""

import gzip
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))

import pytest  # noqa: E402

from harness import tracing  # noqa: E402
from harness.tracing import Event  # noqa: E402

MS = 1e6  # ns


def host(name, start, end, line="python3", **stats):
    return Event(False, line, name, start * MS, (end - start) * MS, stats)


def module(start, end, run_id):
    return Event(True, "XLA Modules", f"jit__lambda({run_id})", start * MS,
                 (end - start) * MS, {"run_id": run_id})


def op(name, start, end):
    return Event(True, "XLA Ops", name, start * MS, (end - start) * MS, {})


def hand_trace():
    """Two steps.  Step 1 (0-10 ms): a draft launch at 1 ms, enqueued in
    the launching call (run 1; ops 2-4 and 3-5 ms inside its module 2-5);
    a verify launch at 6 ms, enqueued later on a worker thread that a
    flow links back to the launch (run 2; op 6-9).  The harness observes
    10-12 ms and waits for an arrival 12-16.  Step 2 (16-20 ms): a verify
    (run 3, op 17-19) and an eager program launched outside any model
    span (run 4, op 19.5-19.8)."""
    return [
        host("bench.step", 0, 10),
        host("bench.program.draft", 1, 1.5),
        host("DoEnqueueProgram", 1.1, 1.2, line="main", run_id=1),
        module(2, 5, 1), op("fusion.1", 2, 4), op("fusion.2", 3, 5),
        host("bench.program.verify", 6, 6.5),
        host("tpu::System::Execute", 6.1, 6.2, line="main", _p=77),
        host("IssueSequencedEvent", 6.6, 7.0, line="worker", _c=77),
        host("DoEnqueueProgram", 6.7, 6.8, line="worker", run_id=2),
        module(6, 9, 2), op("dot.7", 6, 9),
        host("bench.observe", 10, 12),
        host("bench.wait_arrival", 12, 16),
        host("bench.step", 16, 20),
        host("bench.program.verify", 17, 17.2),
        host("DoEnqueueProgram", 17.05, 17.1, line="main", run_id=3),
        module(17, 19, 3), op("dot.7", 17, 19),
        host("DoEnqueueProgram", 19.4, 19.45, line="main", run_id=4),
        module(19.5, 19.8, 4), op("copy.3", 19.5, 19.8),
    ]


def test_busy_is_the_union_of_device_operations():
    r = tracing.reduce_events(hand_trace())
    assert r.window_s == pytest.approx(20e-3)
    # 2-5 (union of 2-4 and 3-5), 6-9, 17-19, 19.5-19.8
    assert r.busy_s == pytest.approx(8.3e-3)
    assert r.steps == 2


def test_device_time_goes_to_the_kind_that_launched_it():
    r = tracing.reduce_events(hand_trace())
    assert r.kind_s["draft"] == pytest.approx(3e-3)
    # run 2 was enqueued on a worker after its span closed: the flow
    # leads back to the launch inside bench.program.verify
    assert r.kind_s["verify"] == pytest.approx(5e-3)
    assert r.kind_s["other"] == pytest.approx(0.3e-3)
    ops = dict(r.breakdown["device_ops"])
    assert ops["verify:dot.7"] == pytest.approx(5e-3)
    assert ops["draft:fusion.1"] == pytest.approx(2e-3)
    assert ops["jit__lambda"] == pytest.approx(0.3e-3)


def test_idle_time_goes_to_the_harness_span_open_over_it():
    r = tracing.reduce_events(hand_trace())
    idle = dict(r.breakdown["idle_gaps"])
    # 0-2, 5-6, 9-10 in step 1; 16-17, 19-19.5, 19.8-20 in step 2
    assert idle["bench.step"] == pytest.approx(5.7e-3)
    assert idle["bench.observe"] == pytest.approx(2e-3)
    assert idle["bench.wait_arrival"] == pytest.approx(4e-3)
    assert sum(idle.values()) == pytest.approx(20e-3 - 8.3e-3)


def test_no_harness_spans_no_numbers():
    r = tracing.reduce_events([op("x", 0, 1)])
    assert r.window_s == 0 and r.steps == 0


def recorded():
    path = os.path.join(TESTS, "data", "trace_step_1.5b.json.gz")
    with gzip.open(path, "rt") as f:
        return [Event(e["device"], e["line"], e["name"], e["start_ns"],
                      e["duration_ns"], e["stats"]) for e in json.load(f)]


def test_one_recorded_step_of_the_chip():
    """One ``SpinEngine.step`` of ``qwen2-1.5b.chat`` at 40 rows, traced
    on a TPU v5e: four draft tokens and a catch-up decode on the 0.5B
    drafter, one packed verify of the 1.5B target."""
    r = tracing.reduce_events(recorded())
    assert r.steps == 1
    assert r.window_s == pytest.approx(0.845898451)
    assert r.busy_s == pytest.approx(0.597505919)
    assert r.kind_s["draft"] == pytest.approx(0.500244343)
    assert r.kind_s["verify"] == pytest.approx(0.079717846)
    assert sum(r.kind_s.values()) <= r.window_s
    idle = dict(r.breakdown["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    assert len(r.breakdown["device_ops"]) == tracing.TOP
