"""Multi-replica router (serving/router.py): dispatch conservation,
single-replica bit-identity with the bare engine, policy balance, KV
spill, draining and deterministic tie-breaking — plus the replica
sub-mesh carving in launch/mesh.py."""

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core import spec_decode as sd
from repro.core.selector import LBSS, SelectorConfig
from repro.data.workloads import make_workload
from repro.launch import mesh as M
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, SpinEngine
from repro.serving.router import Router, RouterConfig

VOCAB = 256


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


def make_engine(models, capacity=4, kv_budget=None, seed=0, **ecfg_kw):
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


def workload(n=6, rate=300.0, seed=11):
    return make_workload("mix", n, VOCAB, seed=seed, scale=0.25, arrival_rate=rate)


# ------------------------------------------------------- N=1 bit-identity --


@pytest.mark.parametrize("policy", ["lot", "p2c", "slo"])
def test_single_replica_router_bit_identical(models, policy):
    """A 1-replica router must add nothing: same tokens, same sim clock,
    same scheduler counters as driving the bare engine directly."""
    bare = make_engine(models, capacity=3, kv_budget=96 * 3)
    reqs = workload()
    bare.add_requests(reqs)
    bare_stats = bare.run(max_slots=200)

    routed = make_engine(models, capacity=3, kv_budget=96 * 3)
    router = Router([routed], RouterConfig(policy=policy, seed=5))
    router.submit(workload())
    rstats = router.run(max_slots=200)

    for rid, r in bare.requests.items():
        assert routed.requests[rid].emitted == r.emitted, rid
    # the full engine stats dict — goodput, latency percentiles, TTFT,
    # switch and scheduler counters — must match field for field
    assert rstats["replica_stats"][0] == bare_stats
    assert rstats["accepted_tokens"] == bare_stats["accepted_tokens"]
    assert rstats["makespan_sim"] == bare_stats["sim_time"]
    assert rstats["dispatched"] == [len(reqs)]


def test_single_replica_bit_identical_chunked_adaptive(models):
    """Bit-identity must survive the chunked-prefill + adaptive-gamma
    engine features (the paths where admission timing is subtlest)."""
    kw = dict(
        capacity=3,
        prefill_chunk=8,
        token_budget=32,
        gamma_policy="adaptive",
        gamma_max=6,
    )
    bare = make_engine(models, **kw)
    bare.add_requests(workload(seed=23))
    bare_stats = bare.run(max_slots=300)

    routed = make_engine(models, **kw)
    router = Router([routed], RouterConfig(policy="lot"))
    router.submit(workload(seed=23))
    rstats = router.run(max_slots=300)

    for rid, r in bare.requests.items():
        assert routed.requests[rid].emitted == r.emitted, rid
    assert rstats["replica_stats"][0] == bare_stats


# ------------------------------------------------------------ conservation --


@pytest.mark.parametrize("policy", ["lot", "p2c", "slo"])
def test_dispatch_conservation_and_losslessness(models, policy):
    """Every request is served by exactly one replica, and sharding the
    stream never changes any request's tokens (speculative decoding is
    lossless per engine, so the dispatch decision must be too)."""
    reqs = workload(n=8, rate=500.0, seed=31)
    ref = make_engine(models, capacity=8)
    ref.add_requests(workload(n=8, rate=500.0, seed=31))
    ref.run(max_slots=200)

    engines = [make_engine(models, capacity=3, seed=i) for i in range(3)]
    router = Router(engines, RouterConfig(policy=policy, seed=7))
    router.submit(reqs)
    st = router.run(max_slots=200)

    owners = {}
    for i, eng in enumerate(engines):
        for rid, r in eng.requests.items():
            assert rid not in owners, f"request {rid} served twice"
            owners[rid] = i
            assert r.done
            want = ref.requests[rid].emitted[: ref.requests[rid].max_new]
            assert r.emitted[: r.max_new] == want
    assert set(owners) == {r.rid for r in reqs}
    assert sum(router.dispatch_count) == len(reqs)
    assert st["finished"] == len(reqs)
    assert st["undispatched"] == 0


# ----------------------------------------------------------------- balance --


def test_lot_balances_skewed_arrivals(models):
    """A burst of same-instant arrivals must spread across replicas under
    least-outstanding-tokens, not pile onto replica 0."""
    reqs = workload(n=9, rate=5000.0, seed=41)  # near-simultaneous burst
    engines = [make_engine(models, capacity=3, seed=i) for i in range(3)]
    router = Router(engines, RouterConfig(policy="lot"))
    router.submit(reqs)
    router.run(max_slots=200)
    counts = router.dispatch_count
    assert sum(counts) == 9
    assert min(counts) >= 2, counts
    assert max(counts) - min(counts) <= 2, counts


def test_p2c_spreads_load(models):
    """Two random probes on free KV must land work on more than one
    replica for a burst (statistical, but deterministic per seed)."""
    reqs = workload(n=9, rate=5000.0, seed=43)
    engines = [make_engine(models, capacity=3, seed=i) for i in range(3)]
    router = Router(engines, RouterConfig(policy="p2c", seed=3))
    router.submit(reqs)
    router.run(max_slots=200)
    counts = router.dispatch_count
    assert sum(counts) == 9
    assert sum(1 for c in counts if c > 0) >= 2, counts


# -------------------------------------------------------------- edge cases --


def test_replicas_drain_on_empty_queues(models):
    """One request, two replicas: the idle replica must not block
    termination or poison the aggregate stats."""
    engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
    router = Router(engines, RouterConfig())
    router.submit(workload(n=1, rate=100.0, seed=51))
    st = router.run(max_slots=100)
    assert st["finished"] == 1
    assert sorted(router.dispatch_count) == [0, 1]
    idle = router.dispatch_count.index(0)
    assert engines[idle].sim_time == 0.0
    assert st["aggregate_goodput_sim"] > 0.0


@pytest.mark.parametrize("policy", ["lot", "p2c", "slo"])
def test_kv_exhausted_replica_spills_no_deadlock(models, policy):
    """A replica whose KV budget is (nearly) exhausted must not absorb
    the stream: new work spills to the roomy replica and everything still
    finishes — per-replica schedulers guarantee progress, the router must
    not defeat them."""
    # replica 0: 2 blocks of 16 cells — one short request fills it.
    # replica 1: ample.
    tight = make_engine(models, capacity=2, kv_budget=32, seed=0)
    roomy = make_engine(models, capacity=4, kv_budget=4 * 128, seed=1)
    router = Router([tight, roomy], RouterConfig(policy=policy, seed=9))
    reqs = workload(n=6, rate=2000.0, seed=61)
    router.submit(reqs)
    st = router.run(max_slots=400)
    assert st["finished"] == len(reqs), router.dispatch_count
    for eng in (tight, roomy):
        for r in eng.requests.values():
            assert r.done
    if policy == "p2c":
        # KV-aware probing must favour the roomy replica for the burst
        # (lot is token-based and splits a same-instant burst evenly —
        # its guarantee here is progress, which the asserts above cover)
        assert router.dispatch_count[1] > router.dispatch_count[0]


def test_dispatch_avoids_budget_exhausted_replicas(models):
    """A replica that spent its run() step budget can never be stepped
    again in this run — dispatching to it would strand the request, so
    _choose must prefer replicas that can still serve (falling back to
    everyone only when nobody has budget)."""
    reqs = workload(n=1, rate=100.0, seed=81)
    for policy in ("lot", "p2c"):
        engines = [make_engine(models, capacity=2, seed=i) for i in range(2)]
        router = Router(engines, RouterConfig(policy=policy, seed=3))
        router._budget = [0, 5]  # replica 0 exhausted mid-run
        assert router._choose(reqs[0]) == 1
        router._budget = [0, 0]  # nobody left: conservation over progress
        assert router._choose(reqs[0]) in (0, 1)


def test_deterministic_dispatch_and_tie_breaking(models):
    """Same (policy, seed, workload) => identical dispatch map; equal
    replica state => lowest index wins."""
    for policy in ("lot", "p2c"):
        maps = []
        for _ in range(2):
            engines = [make_engine(models, capacity=2, seed=i) for i in range(3)]
            router = Router(engines, RouterConfig(policy=policy, seed=13))
            router.submit(workload(n=5, rate=1000.0, seed=71))
            router.run(max_slots=150)
            maps.append(dict(router.dispatched_to))
        assert maps[0] == maps[1], policy
    # lot on untouched equal replicas: first dispatch goes to replica 0
    engines = [make_engine(models, capacity=2, seed=i) for i in range(3)]
    router = Router(engines, RouterConfig(policy="lot"))
    router.submit(workload(n=1, rate=100.0, seed=73))
    router.run(max_slots=100)
    assert router.dispatched_to[0] == 0


def test_router_config_validation(models):
    with pytest.raises(ValueError):
        RouterConfig(policy="round-robin")
    with pytest.raises(ValueError):
        Router([], RouterConfig())
    eng = make_engine(models, capacity=2)
    with pytest.raises(ValueError):
        Router([eng], RouterConfig(), submeshes=[object(), object()], rules={})


# -------------------------------------------------------- replica sub-mesh --


def test_carve_replica_axis_pure_logic():
    """Device-array carving is pure array logic: each replica gets its
    slice, remaining axes keep their order, and every device appears in
    exactly one sub-array."""
    devs = np.arange(2 * 3 * 4).reshape(2, 3, 4)
    parts, names = M.carve_replica_axis(devs, ("replica", "data", "model"))
    assert names == ("data", "model")
    assert len(parts) == 2
    assert parts[0].shape == (3, 4)
    flat = np.sort(np.concatenate([p.ravel() for p in parts]))
    assert (flat == np.arange(24)).all()
    # replica axis not leading: moveaxis, not reshape
    devs = np.arange(3 * 2 * 4).reshape(3, 2, 4)
    parts, names = M.carve_replica_axis(devs, ("data", "replica", "model"))
    assert names == ("data", "model")
    assert len(parts) == 2 and parts[0].shape == (3, 4)
    want = {int(x) for x in devs[:, 0, :].ravel()}
    assert {int(x) for x in parts[0].ravel()} == want
    # no replica axis: the whole array is the single replica
    parts, names = M.carve_replica_axis(devs, ("pod", "data", "model"))
    assert len(parts) == 1 and names == ("pod", "data", "model")


def test_replica_submeshes_single_device():
    """On the 1-CPU test host: a replica-less mesh round-trips, and the
    replicas=1 constructor still builds a usable mesh."""
    mesh = M.make_local_mesh(1, 1)
    assert M.replica_submeshes(mesh) == [mesh]
    assert "replica" not in mesh.axis_names


def test_replica_sharding_trees_rejects_uncarved_mesh():
    from repro.distributed import sharding as shd

    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape

    with pytest.raises(ValueError):
        shd.replica_sharding_trees(
            [FakeMesh({"replica": 2, "model": 2})], shd.serve_rules(), {}, {}
        )
