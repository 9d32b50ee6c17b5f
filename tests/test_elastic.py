"""Elastic fleet chaos/property suite (serving/router.py control plane).

The autoscale/steal/drain control plane moves requests between replicas
while they serve — exactly where requests get silently lost or
double-served.  This suite random-walks adversarial control schedules
(scale-up, drain-before-retire, forced steals) against a diurnal arrival
trace and asserts the conservation invariants at EVERY step:

* request conservation — every submitted rid finishes exactly once,
  never lost across a drain/steal, never owned by two engines;
* stolen requests re-prefill from scratch — a migrating request holds no
  KV row anywhere at the instant it moves;
* drain-before-retire — a replica is only ever ``standby`` with nothing
  outstanding (no rows, no queue, no pendings);
* token-stream equality — wherever a request ends up, and however often
  it was stolen before (or after a preemption released) its prefill, it
  emits the reference engine's greedy continuation token for token;
* bit-identity — with ``autoscale="off"`` and no classes the router is
  the pre-elastic router: same tokens AND sim-clock stats as the bare
  engine, across linear/tree x fused/unfused.

Runs without hypothesis too (tests/hypcompat.py): the random-walk
harness is also driven by fixed example scripts, so a bare environment
still exercises every invariant.  CI runs the ``chaos`` profile (200+
examples, fixed seed, no deadline) on top.
"""

import os

import jax
import numpy as np
import pytest
from hypcompat import HAVE_HYPOTHESIS, given, st

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.gamma import GammaConfig
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import (bursty_arrivals, diurnal_arrivals,
                                  make_workload)
from repro.launch import mesh as M
from repro.launch.serve import split_weighted
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, SpinEngine
from repro.serving.router import (CLASS_KV_WEIGHTS, Router, RouterConfig,
                                  class_engine_config, parse_replica_classes)

if HAVE_HYPOTHESIS:
    # Profiles instead of per-test @settings so the CI chaos step can
    # raise the example count without editing the tests:
    #   HYPOTHESIS_PROFILE=chaos pytest tests/test_elastic.py \
    #       --hypothesis-seed=0
    from hypothesis import settings as hsettings
    hsettings.register_profile("chaos", max_examples=200, deadline=None)
    hsettings.register_profile("dev", max_examples=8, deadline=None)
    hsettings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))

VOCAB = 256
N_REQ = 7


@pytest.fixture(scope="module")
def models():
    key = jax.random.PRNGKey(0)
    cfg_llm = registry.reduced_for(
        "llama-7b", d_model=96, n_heads=4, n_kv_heads=4, vocab_size=VOCAB
    )
    llm = sd.Bundle(cfg_llm, T.init_params(cfg_llm, key))
    ssms = []
    for i, (d, L) in enumerate([(32, 1), (64, 2)]):
        c = registry.reduced_for(
            "llama-68m",
            d_model=d,
            n_heads=4,
            n_kv_heads=4,
            vocab_size=VOCAB,
            n_layers=L,
        )
        ssms.append(sd.Bundle(c, T.init_params(c, jax.random.PRNGKey(i + 1))))
    return llm, ssms


def make_engine(models, capacity=2, kv_budget=None, seed=0, **ecfg_kw):
    llm, ssms = models
    sel = LBSS(
        SelectorConfig(
            n_ssms=len(ssms),
            batch_limits=[capacity] * len(ssms),
            alpha=4,
            beta=2,
            seed=seed,
        )
    )
    ecfg = EngineConfig(
        gamma=3,
        max_len=128,
        capacity=capacity,
        packed_bucket=128,
        straggler_mitigation=False,
        kv_budget=kv_budget,
        seed=seed,
        **ecfg_kw,
    )
    return SpinEngine(llm, ssms, sel, ecfg)


def workload(n=N_REQ, seed=11):
    """Diurnal-stamped request mix: the autoscaling workload (trough at a
    fifth of the peak, ~one day/night cycle over the stream)."""
    reqs = make_workload("mix", n, VOCAB, seed=seed, scale=0.25)
    trace = diurnal_arrivals(
        n, rate_base=60.0, rate_peak=300.0, period=2.0 * n / 300.0, seed=seed
    )
    for r, t in zip(reqs, trace):
        r.arrival = float(t)
    return reqs


_REFERENCE = {}  # workload seed -> {rid: reference emitted tokens}


def reference_tokens(models, seed):
    """The greedy continuation per request, from one big bare engine —
    THE token stream every chaos schedule must reproduce (speculative
    decoding is lossless; scheduling/stealing must be too)."""
    if seed not in _REFERENCE:
        eng = make_engine(models, capacity=N_REQ, seed=0)
        eng.add_requests(workload(seed=seed))
        eng.run(max_slots=400)
        _REFERENCE[seed] = {
            rid: list(r.emitted[: r.max_new])
            for rid, r in eng.requests.items()
        }
    return _REFERENCE[seed]


# ------------------------------------------------------- chaos harness --


def check_invariants(router):
    """The per-step conservation contract."""
    owner = {}
    for i, eng in enumerate(router.engines):
        for rid in eng.requests:
            assert rid not in owner, (
                f"rid {rid} owned by replicas {owner[rid]} and {i}"
            )
            owner[rid] = i
    for i, st_ in enumerate(router.states):
        if st_ == "standby":
            eng = router.engines[i]
            # drain-before-retire: standby means NOTHING outstanding —
            # no rows decoding, no queue, no pending arrivals
            assert not eng.scheduler.outstanding, f"replica {i}"
            assert not eng.scheduler.running, f"replica {i}"


def force_steal(router):
    """Migrate one queued (rowless) request between replicas, bypassing
    the router's cost rule — the adversarial steal.  Asserts the
    no-stale-KV contract at the instant of migration."""
    srcs = [
        i
        for i, e in enumerate(router.engines)
        if e.scheduler.waiting and router.states[i] != "standby"
    ]
    if not srcs:
        return
    src = srcs[0]
    dsts = [i for i in router._actives() if i != src]
    if not dsts:
        return
    dst = dsts[0]
    r = router.engines[src].scheduler.waiting[0]
    assert not any(e.llm_pool.has(r.rid) for e in router.engines), (
        f"queued rid {r.rid} holds a KV row"
    )
    out = router.engines[src].release_queued([r.rid])
    assert [x.rid for x in out] == [r.rid]
    router.engines[dst].add_requests(out)
    router.dispatched_to[r.rid] = dst
    router.steals += len(out)


def drive(router, script, max_iters=5000):
    """The run() co-simulation loop with an adversarial control schedule
    spliced in: each iteration applies the next scripted action (scale
    up / drain / steal / nothing), completes pending drains, checks the
    invariants, then steps the lagging live replica."""
    k = 0
    for it in range(max_iters):
        now = router._fleet_now()
        router._control(now)  # completes drains (autoscale off here)
        if script:
            act = script[k % len(script)]
            k += 1
            actives = router._actives()
            if act == "up":
                standby = [
                    i for i, s in enumerate(router.states) if s == "standby"
                ]
                if standby:
                    router._activate(standby[0], now)
            elif act == "down" and len(actives) > 1:
                router._drain(actives[-1], now)
            elif act == "steal":
                force_steal(router)
        check_invariants(router)
        live = [
            i
            for i, eng in enumerate(router.engines)
            if eng.scheduler.outstanding
        ]
        if not live:
            if router._pending:
                router._dispatch_due(router._pending[0][0])
                continue
            return it
        i = min(live, key=lambda j: (router.engines[j].sim_time, j))
        router._dispatch_due(router.engines[i].sim_time)
        router.step_replica(i)
    raise AssertionError(f"chaos run did not drain in {max_iters} iters")


def run_chaos(models, seed, script):
    reqs = workload(seed=seed)
    engines = [make_engine(models, capacity=2, seed=i) for i in range(3)]
    router = Router(engines, RouterConfig(policy="lot", seed=seed))
    router.submit(reqs)
    drive(router, script)

    # conservation: every rid finished exactly once, somewhere
    finished = [rid for e in engines for rid in e.scheduler.finished]
    assert sorted(finished) == sorted(r.rid for r in reqs), (
        f"finished {sorted(finished)} vs submitted "
        f"{sorted(r.rid for r in reqs)} (steals={router.steals}, "
        f"events={router.events})"
    )
    assert len(finished) == len(set(finished)), "a rid finished twice"
    # token-stream equality: stolen-before-prefill == served in place
    ref = reference_tokens(models, seed)
    for e in engines:
        for rid, r in e.requests.items():
            assert r.done
            assert list(r.emitted[: r.max_new]) == ref[rid], rid
    check_invariants(router)
    return router


# Fixed scripts so the invariants run even without hypothesis: a steal
# storm, a scale thrash, and a mixed schedule.
_EXAMPLE_SCRIPTS = [
    ["steal", "none", "steal"],
    ["down", "none", "up", "none", "down", "steal"],
    ["up", "steal", "down", "none", "steal", "up", "none", "down"],
]


@pytest.mark.parametrize("script", _EXAMPLE_SCRIPTS)
def test_chaos_examples(models, script):
    router = run_chaos(models, seed=11, script=script)
    if script is _EXAMPLE_SCRIPTS[0]:
        # the steal storm must actually exercise migration (the other
        # scripts steal opportunistically — queues may be empty at the
        # scripted instants; test_stolen_before_prefill_token_equality
        # covers the forced path deterministically)
        assert router.steals > 0, "steal storm moved nothing"


@given(
    seed=st.integers(min_value=11, max_value=13),
    script=st.lists(
        st.sampled_from(["none", "up", "down", "steal"]),
        min_size=1,
        max_size=24,
    ),
)
def test_chaos_random_walk(models, seed, script):
    """Hypothesis random-walk: any interleaving of scale-up / drain /
    steal events against the diurnal trace conserves every request and
    reproduces the reference token streams."""
    run_chaos(models, seed=seed, script=script)


# ----------------------------------------------- autoscale-off identity --


@pytest.mark.parametrize(
    "ekw",
    [
        {},
        {"spec_shape": "tree", "spec_branch": 2},
        {"fused_kernels": "on"},
        {"spec_shape": "tree", "spec_branch": 2, "fused_kernels": "on"},
    ],
    ids=["linear", "tree", "linear+fused", "tree+fused"],
)
def test_autoscale_off_bit_identity(models, ekw):
    """--autoscale off --replica-classes '' must be the PR 9 router:
    tokens AND sim-clock stats bit-identical to the bare engine, across
    linear/tree x fused/unfused."""
    bare = make_engine(models, capacity=3, kv_budget=96 * 3, **ekw)
    bare.add_requests(workload())
    bare_stats = bare.run(max_slots=300)

    routed = make_engine(models, capacity=3, kv_budget=96 * 3, **ekw)
    router = Router(
        [routed],
        RouterConfig(policy="lot", autoscale="off", steal="auto", classes=""),
    )
    router.submit(workload())
    rstats = router.run(max_slots=300)

    for rid, r in bare.requests.items():
        assert routed.requests[rid].emitted == r.emitted, rid
    assert rstats["replica_stats"][0] == bare_stats
    assert rstats["makespan_sim"] == bare_stats["sim_time"]
    assert rstats["steals"] == 0
    assert rstats["scale_ups"] == 0 and rstats["scale_downs"] == 0


def test_default_config_is_autoscale_off(models):
    """RouterConfig() defaults must not enable any control-plane action:
    a 2-replica run matches an explicitly-disabled one dispatch for
    dispatch and stat for stat."""
    results = []
    for cfg in (
        RouterConfig(policy="lot"),
        RouterConfig(policy="lot", autoscale="off", steal="off", classes=""),
    ):
        engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
        router = Router(engines, cfg)
        router.submit(workload())
        st_ = router.run(max_slots=300)
        results.append((dict(router.dispatched_to), st_))
    assert results[0][0] == results[1][0]
    assert results[0][1]["replica_stats"] == results[1][1]["replica_stats"]
    assert results[0][1]["accepted_tokens"] == results[1][1]["accepted_tokens"]


# -------------------------------------------- draining exclusion (fix) --


def test_draining_replica_excluded_from_dispatch(models):
    """Regression (ISSUE 10 satellite): _choose used to tie-break onto a
    draining replica; draining replicas must never take new admissions
    while an active replica exists."""
    reqs = workload(n=1, seed=51)
    for policy in ("lot", "p2c", "slo"):
        engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
        router = Router(engines, RouterConfig(policy=policy, seed=3))
        # equal, empty replicas: the old tie-break picks replica 0 —
        # which is exactly the draining one here
        router.states[0] = "draining"
        assert router._choose(reqs[0]) == 1, policy
    # every replica draining: conservation over progress — dispatch
    # must still land somewhere rather than strand the request
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(engines, RouterConfig(policy="lot"))
    router.states = ["draining", "draining"]
    assert router._choose(reqs[0]) in (0, 1)


def test_standby_replica_excluded_from_dispatch(models):
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(engines, RouterConfig(policy="lot"))
    router.states[0] = "standby"
    assert router._choose(workload(n=1, seed=52)[0]) == 1


# ----------------------------------------------------------- autoscaler --


def test_autoscaler_scales_up_and_down_and_conserves(models):
    """Target-occupancy on the diurnal trace: the fleet grows into the
    peak, drains through the trough, finishes everything, and pays
    strictly fewer replica-seconds than the static fleet."""
    n = 12
    reqs = make_workload("mix", n, VOCAB, seed=17, scale=0.25)
    trace = diurnal_arrivals(
        n, rate_base=30.0, rate_peak=200.0, period=2.0 * n / 200.0, seed=17
    )
    for r, t in zip(reqs, trace):
        r.arrival = float(t)
    engines = [make_engine(models, capacity=2, seed=i) for i in range(3)]
    router = Router(
        engines,
        RouterConfig(
            policy="lot",
            autoscale="target-occupancy",
            replicas_min=1,
            replicas_max=3,
            cooldown=0.01,
        ),
    )
    assert router.states == ["active", "standby", "standby"]
    router.submit(reqs)
    st_ = router.run(max_slots=2000)
    assert st_["finished"] == n
    assert st_["scale_ups"] >= 1, router.events
    finished = [rid for e in engines for rid in e.scheduler.finished]
    assert sorted(finished) == list(range(n))
    # cost: strictly cheaper than keeping all three active for the run
    assert st_["replica_seconds"] < 3 * st_["makespan_sim"] - 1e-9
    # drain-before-retire, from the audit trail: every retire followed a
    # drain of the same replica
    drained = set()
    for e in router.events:
        if e["event"] == "drain":
            drained.add(e["replica"])
        if e["event"] == "retire":
            assert e["replica"] in drained
    check_invariants(router)


def test_provisioned_ledger_static_fleet(models):
    """autoscale off: every replica is provisioned for the whole run —
    replica_seconds == n_replicas x makespan, the static cost base."""
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(engines, RouterConfig(policy="lot"))
    router.submit(workload())
    st_ = router.run(max_slots=300)
    assert st_["replica_seconds"] == pytest.approx(2 * st_["makespan_sim"])
    assert st_["cost_normalized_goodput"] == pytest.approx(
        st_["accepted_tokens"] / st_["replica_seconds"]
    )


def test_activation_clock_syncs_forward(models):
    """A replica provisioned at fleet time T serves from T: its sim
    clock never lags the activation instant (no retroactive serving)."""
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(
        engines,
        RouterConfig(
            policy="lot",
            autoscale="target-occupancy",
            replicas_min=1,
            replicas_max=2,
        ),
    )
    engines[0].sim_time = 0.25  # replica 0 has been serving a while
    router._activate(1, router._fleet_now())
    assert engines[1].sim_time == pytest.approx(0.25)
    assert router.states[1] == "active"
    assert router._active_since[1] == pytest.approx(0.25)


# ------------------------------------------------------- steal mechanics --


def test_release_queued_only_rowless(models):
    """release_queued hands back queued/pending requests and scrubs the
    engine-side indexes; row owners stay."""
    eng = make_engine(models, capacity=1, seed=0)
    reqs = workload(n=4, seed=31)
    for r in reqs:
        r.arrival = 0.0
    eng.add_requests(reqs)  # capacity 1: one admitted, three waiting
    admitted = [rid for rid in eng.requests if eng.llm_pool.has(rid)]
    assert len(admitted) == 1
    wait_before = eng.scheduler.queue_wait
    out = eng.release_queued()
    assert sorted(r.rid for r in out) == sorted(
        r.rid for r in reqs if r.rid not in admitted
    )
    # the source never charges wait for work it handed away: the target
    # re-charges the full arrival->admit wait, so the fleet counts each
    # wait exactly once
    assert eng.scheduler.queue_wait == wait_before
    assert eng.scheduler.stolen == len(out)
    for r in out:
        assert r.rid not in eng.requests
        assert not eng.llm_pool.has(r.rid)
    # the engine still drains its row owner
    st_ = eng.run(max_slots=100)
    assert st_["scheduler"]["finished"] == 1


def test_release_queued_include_pending(models):
    eng = make_engine(models, capacity=2, seed=0)
    reqs = workload(n=3, seed=33)
    reqs[0].arrival = 0.0
    reqs[1].arrival = 1e6  # far future: stays pending
    reqs[2].arrival = 1e6
    eng.add_requests(reqs)
    out = eng.release_queued()  # default: arrived-but-rowless only
    assert [r.rid for r in out] == []
    out = eng.release_queued(include_pending=True)
    assert sorted(r.rid for r in out) == [reqs[1].rid, reqs[2].rid]
    assert not eng.scheduler._pending


def test_stolen_before_prefill_token_equality(models):
    """The core steal contract in isolation: steal a request off a hot
    replica before its prefill, serve it cold on another replica, and
    the token stream matches the reference exactly."""
    ref = reference_tokens(models, 11)
    reqs = workload(seed=11)
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(engines, RouterConfig(policy="lot"))
    # pin everything on replica 0 so its queue builds, then steal one
    for r in reqs:
        r.arrival = 0.0  # timing-free: tokens don't depend on arrivals
        router.dispatched_to[r.rid] = 0
    engines[0].add_requests(reqs)
    engines[0].scheduler.poll(0.0)  # arrivals passed: queue materializes
    victim = engines[0].scheduler.steal_candidates()
    assert victim, "capacity 2 with 7 requests must leave a queue"
    rid = victim[0].rid
    out = engines[0].release_queued([rid])
    assert victim[0].prefill_pos == 0 or not engines[0].llm_pool.has(rid)
    engines[1].add_requests(out)
    drive(router, script=[])
    assert rid in engines[1].requests
    for e in engines:
        for r_id, r in e.requests.items():
            assert list(r.emitted[: r.max_new]) == ref[r_id], r_id


# -------------------------------------------------------- replica classes --


def test_parse_replica_classes():
    assert parse_replica_classes("") == []
    assert parse_replica_classes("  ") == []
    assert parse_replica_classes("prefill:1,decode:3") == [
        "prefill",
        "decode",
        "decode",
        "decode",
    ]
    assert parse_replica_classes("general") == ["general"]
    assert parse_replica_classes("decode:2, prefill") == [
        "decode",
        "decode",
        "prefill",
    ]
    with pytest.raises(ValueError):
        parse_replica_classes("turbo:2")
    with pytest.raises(ValueError):
        parse_replica_classes("decode:0")
    with pytest.raises(ValueError):
        parse_replica_classes("decode:x")
    with pytest.raises(ValueError):
        parse_replica_classes(",,")


def test_class_engine_config(models):
    base = EngineConfig(gamma=3, capacity=4, token_budget=32)
    pre = class_engine_config(base, "prefill")
    assert pre.replica_class == "prefill"
    assert pre.prefill_chunk > 0  # chunked ingestion forced on
    assert pre.token_budget == 64  # doubled: chunk grants dominate
    dec = class_engine_config(base, "decode")
    assert dec.replica_class == "decode"
    assert dec.token_budget == base.token_budget
    gen = class_engine_config(base, "general")
    assert gen == base
    with pytest.raises(ValueError):
        class_engine_config(base, "turbo")
    # KV weighting: decode > general > prefill, split conserves the total
    shares = split_weighted(
        1024, [CLASS_KV_WEIGHTS[c] for c in ("prefill", "general", "decode")]
    )
    assert sum(shares) == 1024
    assert shares[0] < shares[1] < shares[2]


def test_prefill_class_caps_adaptive_gamma(models):
    """A prefill-class replica clamps ADAPTIVE speculation shallow (its
    verify budget feeds prompt chunks); fixed policy is untouched —
    the --gamma-policy fixed bit-identity contract."""
    eng = make_engine(
        models,
        capacity=2,
        replica_class="prefill",
        gamma_policy="adaptive",
        gamma_max=6,
    )
    assert eng.gamma_ctl.cfg.depth_cap == 3  # ceil(6 / 2)
    eng_fixed = make_engine(models, capacity=2, replica_class="prefill")
    assert eng_fixed.gamma_ctl.cfg.depth_cap == 2  # ceil(3 / 2), unused
    ids = [0, 1]
    grants = eng.gamma_ctl.grant(ids, {0: 0, 1: 0})
    assert all(g <= 3 for g in grants.values())
    # fixed policy ignores the cap entirely
    grants = eng_fixed.gamma_ctl.grant(ids, {0: 0, 1: 0})
    assert all(g == 3 for g in grants.values())
    eng_gen = make_engine(models, capacity=2)
    assert eng_gen.gamma_ctl.cfg.depth_cap is None
    with pytest.raises(ValueError):
        GammaConfig(depth_cap=0)
    with pytest.raises(ValueError):
        make_engine(models, capacity=2, replica_class="turbo")


def test_class_affine_dispatch(models):
    """Long-prompt requests prefer the prefill replica, decode-heavy
    ones the decode replica; with no matching replica the fleet still
    serves (preference, not partition)."""
    llm, ssms = models
    engines = []
    for i, cls in enumerate(["prefill", "decode"]):
        sel = LBSS(
            SelectorConfig(
                n_ssms=len(ssms), batch_limits=[2] * len(ssms), alpha=4,
                beta=2, seed=i,
            )
        )
        ecfg = class_engine_config(
            EngineConfig(
                gamma=3, max_len=128, capacity=2, packed_bucket=128,
                straggler_mitigation=False, seed=i,
            ),
            cls,
        )
        engines.append(SpinEngine(llm, ssms, sel, ecfg))
    router = Router(engines, RouterConfig(policy="lot"))
    reqs = workload(n=2, seed=61)
    long_prompt, long_out = reqs
    long_prompt.prompt = np.arange(40, dtype=np.int32) % VOCAB
    long_prompt.max_new = 8
    long_out.prompt = np.arange(6, dtype=np.int32) % VOCAB
    long_out.max_new = 20
    assert router._choose(long_prompt) == 0  # prefill replica
    assert router._choose(long_out) == 1  # decode replica
    # a draining preferred replica falls through to the other class
    router.states[1] = "draining"
    assert router._choose(long_out) == 0


# ---------------------------------------------------------- mesh / traces --


def test_elastic_replica_submeshes():
    mesh = M.make_local_mesh(1, 1)
    assert M.elastic_replica_submeshes(mesh, 1) == [mesh]
    with pytest.raises(ValueError):
        M.elastic_replica_submeshes(mesh, 2)  # fleet/mesh mismatch
    with pytest.raises(ValueError):
        M.elastic_replica_submeshes(mesh, 0)


def test_diurnal_arrivals_properties():
    t = diurnal_arrivals(60, rate_base=20.0, rate_peak=200.0, period=1.0,
                         seed=5)
    assert len(t) == 60
    assert np.all(np.diff(t) > 0)  # strictly increasing timestamps
    same = diurnal_arrivals(60, rate_base=20.0, rate_peak=200.0, period=1.0,
                            seed=5)
    assert np.array_equal(t, same)  # deterministic per seed
    other = diurnal_arrivals(60, rate_base=20.0, rate_peak=200.0, period=1.0,
                             seed=6)
    assert not np.array_equal(t, other)
    # the curve starts at the trough: arrivals are denser around the
    # mid-period peak than in the opening trough quarter
    trough = np.sum(t < 0.25)
    peak = np.sum((t >= 0.25) & (t < 0.75))
    assert peak > trough
    with pytest.raises(ValueError):
        diurnal_arrivals(4, rate_base=0.0, rate_peak=10.0, period=1.0)
    with pytest.raises(ValueError):
        diurnal_arrivals(4, rate_base=20.0, rate_peak=10.0, period=1.0)
    with pytest.raises(ValueError):
        diurnal_arrivals(4, rate_base=1.0, rate_peak=2.0, period=0.0)


def test_bursty_arrivals_properties():
    t = bursty_arrivals(80, rate_base=10.0, rate_peak=400.0,
                        burst_every=1.0, burst_len=0.2, seed=7)
    assert len(t) == 80 and np.all(np.diff(t) > 0)
    assert np.array_equal(
        t,
        bursty_arrivals(80, rate_base=10.0, rate_peak=400.0,
                        burst_every=1.0, burst_len=0.2, seed=7),
    )
    # most arrivals land inside the short burst windows
    phase = t % 1.0
    in_burst = np.sum(phase >= 0.8)
    assert in_burst > len(t) / 2
    with pytest.raises(ValueError):
        bursty_arrivals(4, rate_base=1.0, rate_peak=2.0,
                        burst_every=1.0, burst_len=2.0)
    with pytest.raises(ValueError):
        bursty_arrivals(4, rate_base=1.0, rate_peak=2.0,
                        burst_every=0.0, burst_len=0.0)


# ------------------------------------------------------------- validation --


def test_router_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(autoscale="bananas")
    with pytest.raises(ValueError):
        RouterConfig(steal="maybe")
    with pytest.raises(ValueError):
        RouterConfig(replicas_min=0)
    with pytest.raises(ValueError):
        RouterConfig(replicas_min=4, replicas_max=2)
    with pytest.raises(ValueError):
        RouterConfig(occ_low=0.9, occ_high=0.8)
    with pytest.raises(ValueError):
        RouterConfig(cooldown=-1.0)
    with pytest.raises(ValueError):
        RouterConfig(steal_margin=-0.1)
    with pytest.raises(ValueError):
        RouterConfig(classes="turbo:2")
    RouterConfig(autoscale="target-occupancy", replicas_min=2,
                 replicas_max=4, classes="prefill:1,decode:3")


def test_router_rejects_min_above_fleet(models):
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    with pytest.raises(ValueError):
        Router(engines, RouterConfig(replicas_min=3))
