"""The traffic generator, its residents, and the harness's open loop."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import session, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name="chat-sweep"):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_requests():
    a, b = traffic.Schedule(mix(), 2**33 + 1, 1000), \
        traffic.Schedule(mix(), 2**33 + 1, 1000)
    for i in range(80):
        assert a.lengths(i) == b.lengths(i)
        assert a.label(i) == b.label(i)
        assert a.due(i) == b.due(i)
        assert np.array_equal(a.tokens(i), b.tokens(i))


def test_every_seed_does_the_same_work_on_other_tokens():
    m = mix()
    n = m["sizes"]
    a, b = traffic.Schedule(m, 5, 1000), traffic.Schedule(m, 2**32 + 5, 1000)
    la = [a.lengths(i) for i in range(2 * n)]
    assert la == [b.lengths(i) for i in range(2 * n)]
    assert sorted(la[:n]) == sorted(la[n:]) and la[:n] != la[n:]
    assert [a.due(i) for i in range(n + 1)] == [b.due(i) for i in range(n + 1)]
    assert not np.array_equal(a.tokens(0), b.tokens(0))
    assert [a.label(i) for i in range(n)] != [b.label(i) for i in range(n)]


def test_lengths_are_clipped_and_log_normal():
    m = mix()
    m["sizes"] = 64
    t = traffic.size_table(m)
    for col, key in ((0, "prompt"), (1, "output")):
        assert t[:, col].min() >= m[key]["min"]
        assert t[:, col].max() <= m[key]["max"]
        assert np.median(t[:, col]) == pytest.approx(m[key]["median"], rel=0.05)
    m["prompt"]["max"] = 300
    assert traffic.size_table(m)[:, 0].max() == 300


def test_poisson_gaps_have_the_rate():
    m = mix()
    m["rate_per_s"] = 2.5
    g = traffic.gap_table(m)
    assert g.mean() == pytest.approx(0.4)
    s = traffic.Schedule(m, 9, 100)
    n = m["sizes"]
    assert s.due(0) == 0.0
    assert s.due(n) == pytest.approx(n / 2.5)
    assert all(s.due(i + 1) > s.due(i) for i in range(3 * n))


def test_every_window_is_offered_the_rate():
    m = mix("chat-7b")
    s = traffic.Schedule(m, 4, 100)
    due = np.array([s.due(i) for i in range(3 * m["sizes"])])
    # 9.18 arrivals a 51 s window on average; dealt in a random order the
    # same gaps give windows of 3 to 19
    counts = [np.count_nonzero((due >= t) & (due < t + 51.0))
              for t in np.arange(0.0, due[-1] - 51.0, 0.5)]
    assert 6 <= min(counts) and max(counts) <= 12
    assert sorted(traffic.balanced_order(6)) == list(range(6))
    assert list(traffic.balanced_order(8)) == [0, 4, 2, 6, 1, 5, 3, 7]


def test_tokens_cover_the_vocabulary_only():
    s = traffic.Schedule(mix(), 3, 50)
    toks = np.concatenate([s.tokens(i) for i in range(40)])
    assert toks.min() >= 0 and toks.max() < 50
    assert len(toks) == sum(s.lengths(i)[0] for i in range(40))


def test_residents_are_the_sizes_in_flight_at_steady_state():
    m = mix("chat-7b")
    sizes = traffic.size_table(m)
    res = traffic.resident_table(m, sizes)
    assert len(res) == m["resident"]
    # contexts are prompt lengths the warm-up reaches, and each resident
    # still has output to serve, within the mix's longest request
    assert set(res[:, 0]) <= set(sizes[:, 0])
    assert res[:, 1].min() >= 1
    assert (res.sum(1) <= sizes[:, 0].max() + sizes[:, 1].max()).all()
    # drawn in proportion to their outputs: longer outputs are over-
    # represented, and about half of each is left
    full = [o for p, o in sizes]
    assert res[:, 1].mean() < np.mean(full) * 1.2
    a, b = (traffic.Schedule(m, s, 1000) for s in (1, 2**33 + 1))
    n = m["resident"]
    assert [a.lengths(i) for i in range(n)] == [tuple(map(int, r)) for r in res]
    assert [a.lengths(i) for i in range(2 * n)] == \
        [b.lengths(i) for i in range(2 * n)]
    assert a.lengths(n) == tuple(map(int, sizes[a._block(0)[0][0]]))
    assert traffic.resident_table(dict(m, resident=0), sizes).shape == (0, 2)


# ---------------------------------------------------- loops on a fake clock --

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakePool:
    def __init__(self):
        self.lengths = np.zeros(4, np.int64)
        self.row_of = {}


class FakeScheduler:
    def __init__(self):
        self.running = {}
        self.waiting = []


class FakeEngine:
    """Serves up to ``capacity`` requests, one token each per step of
    ``step_s`` seconds on the fake clock."""

    def __init__(self, clock, capacity=4, step_s=0.1):
        self.clock = clock
        self.step_s = step_s
        self.ecfg = type("E", (), {"capacity": capacity})()
        self.gamma_max = 1
        self.llm_pool = FakePool()
        self.scheduler = FakeScheduler()

    def add_requests(self, reqs):
        for r in reqs:
            r.emitted = []
            self.scheduler.waiting.append(r)

    def step(self):
        s = self.scheduler
        while s.waiting and len(s.running) < self.ecfg.capacity:
            r = s.waiting.pop(0)
            s.running[r.rid] = r
        for r in list(s.running.values()):
            r.emitted.append(1)
            if len(r.emitted) - 1 >= r.max_new:
                r.done = True
                del s.running[r.rid]
        self.clock.t += self.step_s
        return {"active": len(s.running), "tokens": 1}


def fake_session(monkeypatch, rate=4.0, resident=0):
    clock = FakeClock()
    monkeypatch.setattr(session, "clock", clock)
    monkeypatch.setattr(session.time, "sleep", clock.sleep)
    m = mix()
    m.update(rate_per_s=rate, resident=resident)
    m["output"] = {"median": 3, "sigma": 0.3, "min": 2, "max": 5}
    sess = session.Session.__new__(session.Session)
    sess.mix = m
    sess.schedule = traffic.Schedule(m, 1, 100)
    sess.requests, sess.live, sess.calls = {}, {}, []
    sess.next_rid = 0
    sess.window_start = sess.window_end = 0.0
    sess.max_lateness = 0.0
    sess.seed = 1
    sess.engine = FakeEngine(clock)
    return sess, clock


class _Req:
    def __init__(self, rid, dataset, difficulty, prompt, max_new):
        self.rid, self.prompt, self.max_new = rid, prompt, max_new
        self.emitted = None
        self.done = False


@pytest.fixture
def fake_request(monkeypatch):
    import types
    mod = types.ModuleType("repro.data.workloads")
    mod.Request = _Req
    for name in ("repro", "repro.data"):
        monkeypatch.setitem(sys.modules, name, types.ModuleType(name))
    monkeypatch.setitem(sys.modules, "repro.data.workloads", mod)


def test_open_loop_sends_on_schedule(monkeypatch, fake_request):
    sess, clock = fake_session(monkeypatch, rate=4.0)
    sess.start_traffic()
    sess.drive(clock.t + 10.0)
    recs = sorted(sess.requests.values(), key=lambda r: r.rid)
    # sent once due; a step of 0.1 s may carry the clock past the end
    assert sum(1 for i in range(200) if sess.schedule.due(i) < 10.0 - 0.1) \
        <= len(recs) <= sum(1 for i in range(200)
                            if sess.schedule.due(i) < 10.0 + 0.1)
    for r in recs:
        assert r.due == pytest.approx(sess.traffic_start
                                      + sess.schedule.due(r.rid))
    first = [r for r in recs if r.first_token is not None]
    assert first and all(r.first_token >= r.due for r in first)
    assert any(r.finished is not None for r in recs)


def test_residents_are_in_flight_before_the_arrivals_start(monkeypatch,
                                                           fake_request):
    sess, clock = fake_session(monkeypatch, rate=4.0, resident=3)
    t0 = clock.t
    sess.start_traffic()
    res = [sess.requests[i] for i in range(3)]
    assert all(r.first_token is not None and r.due == t0 for r in res)
    assert sess.traffic_start >= max(r.first_token for r in res)
    assert sess.next_rid == 3
    sess.drive(sess.traffic_start + 5.0)
    arrivals = sorted((r for r in sess.requests.values() if r.rid >= 3),
                      key=lambda r: r.rid)
    assert arrivals
    for r in arrivals:
        assert r.due == pytest.approx(sess.traffic_start
                                      + sess.schedule.due(r.rid - 3))
        assert (r.prompt_len, r.output_len) == sess.schedule.lengths(r.rid)
