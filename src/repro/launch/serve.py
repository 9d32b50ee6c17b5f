"""SPIN serving launcher.

    python -m repro.launch.serve --dataset mix --requests 16 \
        --selector lbss --gamma 4 [--no-packed] [--no-pipeline] \
        [--arrival-rate 200] [--kv-budget 512] [--scheduler continuous] \
        [--kv-layout paged|dense] [--block-size 16] \
        [--replicas 2 --router-policy lot]

Builds the heterogeneous SSM zoo + LLM (reduced configs by default, for
CPU runs; ``chip_smoke.py`` at the repo root hands :func:`build_server`
the published LLaMA widths and runs them on one TPU chip), then drives
the continuous-batching scheduler loop until the request stream drains.
Everything runs in one process on its first device: replicas are not yet
placed on chips or mesh slices of their own.  ``--arrival-rate`` turns
the workload into a streaming Poisson arrival process (requests/sec on
the sim clock); without it every request arrives at t=0.  ``--scheduler
static`` keeps the seed-style gang-scheduled cohort baseline for
comparison.

``--replicas N`` serves the stream through N independent engine replicas
behind a router (serving/router.py): ``--capacity`` and ``--kv-budget``
are *aggregate* figures split evenly across replicas, so a replica-count
sweep compares at fixed total resources.  Every flag is documented with
its defaults and interactions in docs/SERVING.md (CI keeps the two in
sync — see tools/check_docs.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from repro.configs import spin_llama
from repro.core import decompose as D
from repro.core import spec_decode as sd
from repro.core.selector import (LBSS, EpsilonGreedy, GreedyPromptLength,
                                 SelectorConfig)
from repro.data.workloads import (bursty_arrivals, diurnal_arrivals,
                                  make_workload)
from repro.models import transformer as T
from repro.models.config import reduced
from repro.serving.engine import EngineConfig, SpinEngine
from repro.serving.router import (CLASS_KV_WEIGHTS, Router, RouterConfig,
                                  class_engine_config, parse_replica_classes)


# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed, git-ignored directory of the checkout (the path is
# part of the cache key, so it must not move between runs)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is
    (JAX reads it itself); otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Call before the first compile: JAX
    settles on a cache once per process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_zoo(vocab: int, seed: int = 0, n_ssms: int = 3,
              llm_cfg=None, ssm_cfgs=None):
    """LLM + heterogeneous SSM zoo.  By default a reduced-scale LLaMA-7B
    and ``n_ssms`` reduced drafters shape-faithful to the paper's LLaMA
    68M..1.4B lineup (CPU runs); ``llm_cfg``/``ssm_cfgs`` build the given
    configs instead, e.g. the published widths.  Weights are random: the
    LLM's from ``seed``, drafter i's from key i + 1.  Every model must
    have the ``vocab``-token vocabulary, since drafts are verified token
    for token."""
    if llm_cfg is None:
        llm_cfg = reduced(spin_llama.LLAMA_7B, d_model=96, n_heads=4,
                          n_kv_heads=4, vocab_size=vocab, n_layers=4)
    if ssm_cfgs is None:
        dims = [(32, 1), (48, 2), (64, 2), (96, 3), (96, 4)][:n_ssms]
        ssm_cfgs = [reduced(spin_llama.SSM_ZOO[min(i, 4)], d_model=d,
                            n_heads=4, n_kv_heads=4, vocab_size=vocab,
                            n_layers=L)
                    for i, (d, L) in enumerate(dims)]
    for c in [llm_cfg, *ssm_cfgs]:
        if c.vocab_size != vocab:
            raise ValueError(f"{c.name} has vocab {c.vocab_size}, the zoo "
                             f"serves vocab {vocab}")
    llm = sd.Bundle(llm_cfg, T.init_params(llm_cfg, jax.random.PRNGKey(seed)))
    ssms = [sd.Bundle(c, T.init_params(c, jax.random.PRNGKey(i + 1)))
            for i, c in enumerate(ssm_cfgs)]
    return llm, ssms


def make_selector(kind: str, n_ssms: int, cap: int, prompt_lens=None,
                  seed: int = 0, group_of=None):
    scfg = SelectorConfig(n_ssms=n_ssms, batch_limits=[cap] * n_ssms,
                          alpha=6, beta=2, seed=seed)
    if kind == "lbss":
        return LBSS(scfg, group_of=group_of)
    if kind == "eps":
        return EpsilonGreedy(scfg, eps=0.2)
    if kind == "greedy":
        return GreedyPromptLength(scfg, prompt_lens or {})
    raise ValueError(kind)


def split_evenly(total: int, n: int):
    """Split an aggregate resource into n near-equal shares (remainder
    to the first replicas) — used so ``--capacity`` and ``--kv-budget``
    stay *aggregate* figures under ``--replicas``.  Shares are zero when
    ``total < n``; callers must validate that every replica gets a
    usable share (serve.py errors out for both budgets)."""
    base, rem = divmod(int(total), n)
    return [base + (1 if i < rem else 0) for i in range(n)]


def split_weighted(total: int, weights):
    """Split an aggregate resource proportionally to integer weights
    (largest-remainder rounding, ties to the lower index) — the
    heterogeneous-fleet KV split: a ``decode`` replica holds
    long-resident contexts and takes a bigger share than a ``prefill``
    replica that turns its cache over per chunk."""
    wsum = sum(weights)
    raw = [int(total) * w / wsum for w in weights]
    shares = [int(x) for x in raw]
    rem = int(total) - sum(shares)
    order = sorted(range(len(weights)),
                   key=lambda i: (-(raw[i] - shares[i]), i))
    for i in order[:rem]:
        shares[i] += 1
    return shares


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mix",
                    choices=["alpaca", "cp", "cip", "mix"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--selector", default="lbss",
                    choices=["lbss", "eps", "greedy"])
    ap.add_argument("--n-ssms", type=int, default=3)
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculation depth: the uniform per-request depth "
                         "under --gamma-policy fixed, the cold-start "
                         "default under adaptive")
    ap.add_argument("--gamma-policy", default="fixed",
                    choices=["fixed", "adaptive"],
                    help="fixed: draft --gamma tokens for every request "
                         "every slot (seed behaviour, bit-identical); "
                         "adaptive: per-request expected-goodput depth in "
                         "[1, --gamma-max] from the selector's acceptance "
                         "estimates, load-capped under --token-budget")
    ap.add_argument("--gamma-max", type=int, default=None,
                    help="adaptive speculation-depth cap (KV margins and "
                         "admission reserve this worst case); default "
                         "2 * --gamma")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--no-packed", action="store_true")
    ap.add_argument("--no-pipeline", action="store_true")
    ap.add_argument("--max-slots", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=0.3)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrival rate (req/s, sim clock); "
                         "default: all requests arrive at t=0")
    ap.add_argument("--capacity", type=int, default=None,
                    help="LLM pool rows (default: --requests)")
    ap.add_argument("--kv-budget", type=int, default=None,
                    help="total KV cells before preemption kicks in "
                         "(paged layout: rounded down to whole blocks and "
                         "enforced as the physical block pool)")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "dense"],
                    help="KV memory layout: block-table paging (default) "
                         "or the legacy dense capacity x max_len grid")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV cells per physical block (paged layout); "
                         "128 matches TPU tile granularity at full scale, "
                         "16 keeps reduced CPU runs snappy")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: max prompt tokens ingested per "
                         "request per slot, interleaved with decode "
                         "(Sarathi-style); 0 = monolithic prefill-on-admit")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-slot LLM query-token budget shared between "
                         "decode slots (gamma+1 tokens each) and prefill "
                         "chunks; default: unthrottled")
    ap.add_argument("--spec-shape", default="linear",
                    choices=["linear", "tree"],
                    help="speculation shape: linear drafts one chain per "
                         "request; tree splits each granted depth across "
                         "up to --spec-branch branches (the drafter's "
                         "top-k first-step candidates), forks the paged "
                         "KV row copy-on-write per branch and verifies "
                         "the whole token tree in one packed pass (needs "
                         "--kv-layout paged and packed verification; "
                         "falls back to linear with a warning otherwise)")
    ap.add_argument("--spec-branch", type=int, default=2,
                    help="tree-speculation branching factor (only with "
                         "--spec-shape tree); 1 is bit-identical to "
                         "linear; gamma_max + branches must fit the "
                         "ancestor-mask node budget "
                         f"({D.max_tree_nodes()} nodes)")
    ap.add_argument("--fused-kernels", default="off",
                    choices=["on", "off"],
                    help="route the paged decode/verify hot path through "
                         "the fused single-launch Pallas kernels "
                         "(kernels/fused_decode.py, fused_verify.py) at "
                         "the default tile shapes "
                         "(kernels/autotune.DEFAULT_CONFIG); off keeps "
                         "the gather + paged-attention path "
                         "bit-identically (needs --kv-layout paged; falls "
                         "back with a warning otherwise)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8", "fp8"],
                    help="paged-KV block storage dtype: bf16 stores the "
                         "model's compute dtype (bit-identical default); "
                         "int8/fp8 store quantized blocks with per-(slot, "
                         "head) float32 scale sidecars — 2-4x more "
                         "resident contexts per --kv-budget, dequant "
                         "fused into the attention kernels (needs "
                         "--kv-layout paged; falls back to bf16 with a "
                         "warning otherwise)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="independent engine replicas behind the router "
                         "(serving/router.py); --capacity and --kv-budget "
                         "are aggregate and split evenly across replicas")
    ap.add_argument("--router-policy", default=None,
                    choices=["lot", "p2c", "slo"],
                    help="replica dispatch policy: lot = least outstanding "
                         "tokens (default), p2c = power-of-two-choices on "
                         "free KV blocks, slo = most cluster-level SLO "
                         "headroom (deadline slack net of backlog drain "
                         "time); passing this flag routes even a "
                         "single replica through the router (bit-identical "
                         "to the bare engine)")
    ap.add_argument("--slo-profile", default="off",
                    choices=["off", "strict", "lax", "interactive"],
                    help="stamp per-class SLO contracts "
                         "(TTFT deadline + per-token target, "
                         "data/workloads.py SLO_PROFILES) onto the "
                         "workload and make admission order, prefill "
                         "chunk sizing, adaptive speculation depth and "
                         "slo routing deadline-aware; off (default) "
                         "stamps nothing and is bit-identical to the "
                         "deadline-blind engine")
    ap.add_argument("--slo-scale", type=float, default=1.0,
                    help="multiply every --slo-profile deadline (>1 lax, "
                         "<1 strict) — one profile serves "
                         "differently-calibrated cost models")
    ap.add_argument("--arrival-pattern", default="poisson",
                    choices=["poisson", "diurnal", "bursty"],
                    help="shape of the --arrival-rate stream: poisson = "
                         "constant-rate (default); diurnal = sinusoidal "
                         "day/night curve between --arrival-rate (peak) "
                         "and a fifth of it (trough); bursty = quiet "
                         "baseline with periodic full-rate bursts — the "
                         "autoscaling workloads (data/workloads.py "
                         "diurnal_arrivals / bursty_arrivals); both need "
                         "--arrival-rate")
    ap.add_argument("--autoscale", default="off",
                    choices=["off", "target-occupancy"],
                    help="elastic fleet control (serving/router.py): off "
                         "(default) keeps every replica serving for the "
                         "whole run, bit-identical to the pre-elastic "
                         "router; target-occupancy scales the active set "
                         "between --replicas-min and --replicas-max "
                         "against mean KV occupancy, backlog and SLO "
                         "headroom, with drain-before-retire")
    ap.add_argument("--replicas-min", type=int, default=1,
                    help="smallest active fleet the autoscaler may drain "
                         "down to (only with --autoscale)")
    ap.add_argument("--replicas-max", type=int, default=None,
                    help="largest active fleet the autoscaler may grow to; "
                         "this many engines and mesh sub-slices are "
                         "pre-carved up front (idle ones cost nothing on "
                         "the provisioning ledger); default: --replicas")
    ap.add_argument("--steal", default="auto",
                    choices=["auto", "on", "off"],
                    help="work stealing of queued, not-yet-prefilled "
                         "requests from hot replicas to the least-loaded "
                         "one when re-prefilling there beats the expected "
                         "wait (no KV migrates); auto (default) = on "
                         "exactly when --autoscale is")
    ap.add_argument("--replica-classes", default="",
                    help="heterogeneous fleet spec, e.g. "
                         "'prefill:1,decode:3': per-class engine configs "
                         "(prefill-heavy: forced chunking + doubled "
                         "--token-budget + shallow adaptive speculation; "
                         "decode: KV-weighted share of --kv-budget) with "
                         "class-affine dispatch — long-prompt requests "
                         "prefer prefill replicas, long-output ones "
                         "decode replicas; empty (default) = homogeneous "
                         "fleet, bit-identical to no classes; the spec's "
                         "total must match --replicas when both are given")
    return ap


def build_server(argv=None, zoo=None):
    """Parse ``argv`` and build what :func:`main` runs: returns
    ``(server, requests, args)`` where ``server`` is a ``SpinEngine`` or,
    for a fleet, a ``Router``, with ``requests`` already submitted.
    ``zoo`` is an ``(llm, ssms)`` pair from :func:`build_zoo`; by default
    the reduced zoo at ``--vocab`` is built."""
    ap = build_parser()
    args = ap.parse_args(argv)
    # flag translation + cross-flag validation live in the configs'
    # from_args constructors (serving/engine.py et al.) — ONE place tests
    # and benchmarks share; this launcher only maps ValueError to the
    # argparse exit and validates the cluster-level (multi-config) splits
    try:
        base_ecfg = EngineConfig.from_args(args)
        rcfg = RouterConfig.from_args(args)
    except ValueError as e:
        ap.error(str(e))
    if args.arrival_rate is not None and args.arrival_rate <= 0:
        ap.error("--arrival-rate must be positive (omit it for "
                 "all-at-t=0 arrivals)")
    if args.capacity is not None and args.capacity <= 0:
        ap.error("--capacity must be positive")
    if args.replicas <= 0:
        ap.error("--replicas must be positive")
    if args.slo_scale <= 0:
        ap.error("--slo-scale must be positive")

    # fleet shape: --replica-classes may define the replica count on its
    # own (--replicas 1 default), and the elastic fleet pre-carves
    # --replicas-max engines up front (launch.mesh.elastic_replica_
    # submeshes on a pod) — standby engines cost nothing on the
    # provisioning ledger until the autoscaler activates them
    classes = parse_replica_classes(args.replica_classes)
    n_rep = args.replicas
    if classes:
        if args.replicas != 1 and len(classes) != args.replicas:
            ap.error(f"--replica-classes carves {len(classes)} replicas "
                     f"but --replicas says {args.replicas} — drop one "
                     "flag or make them agree")
        n_rep = len(classes)
    n_eng = args.replicas_max if args.replicas_max is not None else n_rep
    if n_eng < n_rep:
        ap.error(f"--replicas-max {n_eng} is below the fleet size "
                 f"{n_rep}")
    if classes and len(classes) != n_eng:
        ap.error(f"--replica-classes carves {len(classes)} replicas but "
                 f"the pre-carved fleet is {n_eng} (--replicas-max) — "
                 "give every slot a class")
    if args.replicas_min > n_eng:
        ap.error(f"--replicas-min {args.replicas_min} exceeds the "
                 f"pre-carved fleet of {n_eng}")
    if not classes:
        classes = ["general"] * n_eng

    arrival_rate, arrival_trace = args.arrival_rate, None
    if args.arrival_pattern != "poisson":
        if args.arrival_rate is None:
            ap.error("--arrival-pattern diurnal/bursty needs "
                     "--arrival-rate (the peak rate)")
        # span: the seconds a constant peak-rate stream would cover;
        # diurnal runs ~one day/night cycle over ~2x that, bursty fires
        # one burst per span
        span = args.requests / args.arrival_rate
        if args.arrival_pattern == "diurnal":
            arrival_trace = diurnal_arrivals(
                args.requests, rate_base=args.arrival_rate / 5.0,
                rate_peak=args.arrival_rate, period=2.0 * span,
                seed=args.seed ^ 0xD1A)
        else:
            arrival_trace = bursty_arrivals(
                args.requests, rate_base=args.arrival_rate / 5.0,
                rate_peak=args.arrival_rate, burst_every=span,
                burst_len=span / 4.0, seed=args.seed ^ 0xB5B)
        arrival_rate = None

    if zoo is not None and zoo[0].cfg.vocab_size != args.vocab:
        ap.error(f"--vocab {args.vocab} differs from the zoo's vocab "
                 f"{zoo[0].cfg.vocab_size}")
    use_compile_cache()
    llm, ssms = zoo or build_zoo(args.vocab, args.seed, args.n_ssms)
    reqs = make_workload(args.dataset, args.requests, args.vocab,
                         seed=args.seed, scale=args.scale,
                         arrival_rate=arrival_rate,
                         arrival_trace=arrival_trace,
                         slo_profile=args.slo_profile,
                         slo_scale=args.slo_scale)
    capacity = base_ecfg.capacity
    if n_eng > capacity:
        ap.error(f"a fleet of {n_eng} exceeds the aggregate --capacity "
                 f"{capacity}: every replica needs at least one pool row")
    if (n_eng > 1 and args.kv_budget is not None
            and args.kv_budget < n_eng * args.block_size):
        ap.error(f"--kv-budget {args.kv_budget} is below one "
                 f"--block-size ({args.block_size}) block per replica: "
                 "a zero-block share degenerates that replica to "
                 "one-request-at-a-time service")

    def make_engine(cap: int, kv_budget, seed: int, cls: str) -> SpinEngine:
        sel = make_selector(args.selector, len(ssms), cap,
                            {r.rid: r.prompt_len for r in reqs}, seed,
                            group_of={r.rid: r.dataset for r in reqs})
        ecfg = dataclasses.replace(
            class_engine_config(base_ecfg, cls),
            capacity=cap, kv_budget=kv_budget, seed=seed)
        return SpinEngine(llm, ssms, sel, ecfg)

    if (n_eng > 1 or args.router_policy is not None
            or args.autoscale != "off"):
        # multi-replica path: aggregate capacity / KV budget split across
        # the pre-carved fleet (evenly, or KV-weighted by class); the
        # zoo's Bundles (weights + jit caches) are shared, pools and
        # selectors are per replica
        caps = split_evenly(capacity, n_eng)
        if args.kv_budget is None:
            kvs = [None] * n_eng
        elif any(c != "general" for c in classes):
            kvs = split_weighted(args.kv_budget,
                                 [CLASS_KV_WEIGHTS[c] for c in classes])
        else:
            kvs = split_evenly(args.kv_budget, n_eng)
        engines = [make_engine(caps[i], kvs[i], args.seed, classes[i])
                   for i in range(n_eng)]
        server = Router(engines, rcfg)
        server.submit(reqs)
    else:
        server = make_engine(capacity, args.kv_budget, args.seed, classes[0])
        server.add_requests(reqs)
    return server, reqs, args


def main(argv=None):
    server, _, args = build_server(argv)
    stats = server.run(max_slots=args.max_slots)
    print(json.dumps(stats, indent=2, default=str))
    return stats


if __name__ == "__main__":
    main()
