"""SPIN's runtime engine (paper §III Fig. 7 + §V) with continuous batching.

Per time slot:
  0. the continuous-batching scheduler (serving/scheduler.py) admits
     arrived requests into free pool rows and preempts lowest-priority
     requests when the KV budget is exceeded.  With ``prefill_chunk=0``
     admission prefills the whole prompt monolithically; with
     ``prefill_chunk>0`` the scheduler's token-budget step planner grants
     prompt *chunks* instead — an admitted request holds a row in the
     ``prefilling`` state (partial KV, not drafting) and its chunks are
     appended into the existing row/block table while other slots keep
     decoding in the same step (Sarathi-style mixed batches; under the
     paged layout a chunk allocates exactly its blocks and writes through
     the row's block table);
  1. the selector assigns each active request to an SSM (LBSS / baselines);
     switches go through the SwitchManager (fast pre-computed switching);
  2. the gamma controller (core/gamma.py) grants every request a
     speculation depth k_i in [1, gamma_max] — ``fixed`` policy: the
     uniform ``gamma`` everywhere (bit-identical to the pre-controller
     engine); ``adaptive``: expected-goodput argmax over the selector's
     per-(request, SSM) acceptance estimates, with a load-aware cap when
     the step planner's token budget is contended;
  3. every SSM drafts its rows' granted depths (static-shape pools at the
     slot's max depth; tail positions beyond a row's grant are masked);
  4. the LLM verifies all candidates — padded (vanilla) or packed via
     request decomposition (§V-A) — accepting at most k_i per row;
  5. accepted tokens are committed, caches rolled back, goodput and
     acceptance observed back into the selector; rows of finished requests
     are recycled and immediately re-filled from the waiting queue (same
     step).

The engine clock is the simulated time: requests whose ``arrival``
timestamp lies in the future stay queued until the clock reaches them,
and when the pool drains the clock fast-forwards to the next arrival.

Timing: functional results are exact; the slot TIMELINE (draft/verify
overlap with micro-batch pipelining, §V-B) is computed by the calibrated
event simulator in core/pipeline.py, because this host has one CPU — on a
TPU pod the same schedule is realized by dispatching drafts and
verifications to disjoint device groups (launch/serve.py maps SSM replicas
and the LLM onto sub-meshes; JAX async dispatch overlaps them).  Wall-clock
is also recorded for reference.

Fault tolerance: ``fail_ssm`` drops a replica (requests re-routed through
the switching path); straggler mitigation re-dispatches micro-batches whose
simulated draft time exceeds ``straggler_factor`` x the expected time.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decompose as D
from repro.core import pipeline as P
from repro.core import spec_decode as sd
from repro.core.gamma import GammaConfig, GammaController
from repro.core.switching import SwitchManager
from repro.data.workloads import Request
from repro.kernels import autotune, quant
from repro.models import transformer as T
from repro.serving.paged import paged_compatible
from repro.serving.pool import DenseCachePool, PagedCachePool
from repro.serving.scheduler import ContinuousScheduler, SchedulerConfig
from repro.serving.stats import (EngineStats, annotate,
                                 expected_time_per_token, slo_headroom,
                                 slo_summary, span)


def _bucket(n: int, align: int = 16) -> int:
    return max(align, int(math.ceil(n / align) * align))


def catchup_rows(ssm_rows: Dict, llm_rows: Dict, capacity: int) -> np.ndarray:
    """Target pool row of each drafter pool row's request: -1 for a
    drafter row that holds no request, or whose request holds no target
    row."""
    rows = np.full(capacity, -1, np.int32)
    for rid, row in ssm_rows.items():
        rows[row] = llm_rows.get(rid, -1)
    return rows


@jax.jit
def catchup_inputs(out_all, n_acc_all, rows):
    """A drafter pool's catch-up input, gathered on the device from
    verify's per-row results: drafter row r takes target row ``rows[r]``'s
    emitted tokens and accepted count, zeros where ``rows[r]`` is -1
    (``catchup_rows``)."""
    hit = rows >= 0
    src = jnp.maximum(rows, 0)
    outs = jnp.where(hit[:, None], out_all[src], 0).astype(jnp.int32)
    nacc = jnp.where(hit, n_acc_all[src], 0).astype(jnp.int32)
    return outs, nacc


@dataclasses.dataclass(kw_only=True)
class EngineConfig:
    """Keyword-only on purpose (like ``SchedulerConfig``): fields are
    appended as the engine grows and positional construction would
    silently shift arguments."""
    gamma: int = 4
    # speculation-depth policy (core/gamma.py): "fixed" drafts gamma tokens
    # for every request every slot (bit-identical to the pre-controller
    # engine); "adaptive" grants each request k in [1, gamma_max] by
    # expected-goodput argmax over the selector's acceptance estimates.
    gamma_policy: str = "fixed"
    # adaptive depth cap; None -> 2 * gamma ("fixed" always uses gamma).
    # Pools, KV margins and admission reserve this worst case.
    gamma_max: Optional[int] = None
    max_len: int = 256
    capacity: int = 16                 # concurrent requests (LLM pool rows)
    use_packed_verify: bool = True
    use_pipeline: bool = True
    micro_batches: Optional[List[int]] = None   # None -> paper heuristic
    packed_bucket: int = 256           # packed-KV shape bucketing (retraces)
    straggler_factor: float = 4.0
    straggler_mitigation: bool = True
    seed: int = 0
    # continuous-batching scheduler
    scheduler_policy: str = "continuous"   # or "static" (gang baseline)
    # total KV cells before preemption; None -> capacity*max_len, which
    # never binds (add_requests caps each request at max_len cells)
    kv_budget: Optional[int] = None
    # KV memory layout: "paged" = block-table pools, budget enforced as
    # physical blocks (kv_budget // block_size); "dense" = legacy
    # capacity x max_len grids.  Models with recurrent state or sliding
    # windows fall back to dense automatically.
    kv_layout: str = "paged"
    block_size: int = 16
    # chunked prefill: max prompt tokens ingested per request per slot
    # (0 = monolithic prefill-on-admit).  Continuous policy +
    # attention-family LLM only — recurrent-state LLMs fall back to
    # monolithic automatically (their state updates are not
    # segment-maskable, so bucket-padded chunk appends would corrupt them).
    prefill_chunk: int = 0
    # per-slot LLM query-token budget split between decode slots
    # (gamma+1 tokens each) and prefill chunks; None = unthrottled
    token_budget: Optional[int] = None
    # speculation shape: "linear" drafts one chain per request (the
    # classic SPIN iteration); "tree" splits each granted depth k across
    # up to ``spec_branch`` branches (the drafter's top-k step-1
    # candidates), forks the request's paged KV row copy-on-write per
    # branch, and verifies the whole token tree in ONE packed pass with a
    # topology-aware mask — the longest verified root-to-leaf path wins.
    # Tree mode needs the paged layout + packed verification; otherwise
    # it falls back to linear with a warning (like the paged->dense
    # auto-fallback).  spec_branch=1 is bit-identical to linear.
    spec_shape: str = "linear"
    spec_branch: int = 2
    # fused speculative-step Pallas kernels (kernels/fused_decode.py /
    # fused_verify.py): "on" streams KV straight from the paged pool in a
    # single launch per attention site, with the default tile shapes
    # (kernels/autotune.DEFAULT_CONFIG); "off" keeps the gather +
    # paged-kernel path bit-identically.  Requires the paged layout; "on"
    # under a dense fallback warns and stays unfused.
    fused_kernels: str = "off"
    # paged-KV block storage dtype (kernels/quant.py): "bf16" stores the
    # model's compute dtype (bit-identical default); "int8"/"fp8" store
    # quantized blocks with per-(slot, head) float32 scale sidecars —
    # 2-4x more resident contexts at the same physical KV budget, with
    # dequant fused into the attention kernels.  Requires the paged
    # layout; a quantized choice under the dense fallback warns and
    # reverts to bf16.
    kv_dtype: str = "bf16"
    # SLO-aware serving: when True, requests carrying a Request.slo
    # contract steer admission order, prefill chunk sizing and (under the
    # adaptive gamma policy) speculation depth.  Requests WITHOUT a
    # contract are handled identically either way, so True with an
    # SLO-free workload is bit-identical to False — the
    # `--slo-profile off` contract.
    slo_aware: bool = True
    # heterogeneous replica class (elastic fleet, serving/router.py):
    # "general" serves everything (the default, bit-identical to the
    # class-free engine); "prefill" is tuned for prompt ingestion (the
    # router steers long-prompt requests here and ADAPTIVE gamma grants
    # are capped shallow so verify budget feeds chunks); "decode" is
    # tuned for flat TPOT on short-prompt/long-output streams.  The class
    # itself never changes engine semantics — only which knob preset the
    # router carves (see router.class_engine_config) plus the gamma cap.
    replica_class: str = "general"

    @classmethod
    def from_args(cls, args, *, capacity=None, kv_budget=None, seed=None):
        """Build an EngineConfig from a ``launch.serve.build_parser()``
        namespace — THE flag translation, shared by serve.py, tests and
        benchmarks so nobody re-derives it by hand.  ``capacity`` /
        ``kv_budget`` override the per-replica share (serve.py splits the
        aggregate flags across replicas); cross-flag validation lives
        here and raises ``ValueError`` (serve.py maps it to
        ``parser.error``)."""
        if args.block_size <= 0:
            raise ValueError("--block-size must be positive")
        if args.prefill_chunk < 0:
            raise ValueError(
                "--prefill-chunk must be >= 0 (0 disables chunking)")
        if args.token_budget is not None and args.token_budget <= 0:
            raise ValueError("--token-budget must be positive (omit it "
                             "for unthrottled slots)")
        if args.gamma <= 0:
            raise ValueError("--gamma must be positive")
        if args.gamma_max is not None and args.gamma_max <= 0:
            raise ValueError(
                "--gamma-max must be positive (omit it for 2 * --gamma)")
        if args.spec_branch < 1:
            raise ValueError("--spec-branch must be >= 1")
        if args.spec_shape == "tree":
            gmax = (args.gamma if args.gamma_policy == "fixed"
                    else (args.gamma_max if args.gamma_max is not None
                          else 2 * args.gamma))
            max_nodes = D.max_tree_nodes()
            if gmax + min(args.spec_branch, gmax) > max_nodes:
                raise ValueError(
                    f"--spec-shape tree needs gamma_max + branches <= "
                    f"{max_nodes} tree nodes for the "
                    f"{D.ANCESTOR_MASK_BITS}-bit ancestor mask (got "
                    f"--gamma-max {gmax}, --spec-branch "
                    f"{args.spec_branch}); lower one of them")
        return cls(
            gamma=args.gamma, gamma_policy=args.gamma_policy,
            gamma_max=args.gamma_max, max_len=256,
            capacity=(capacity if capacity is not None
                      else (args.capacity if args.capacity is not None
                            else args.requests)),
            use_packed_verify=not args.no_packed,
            use_pipeline=not args.no_pipeline,
            scheduler_policy=args.scheduler,
            kv_budget=kv_budget if kv_budget is not None else args.kv_budget,
            kv_layout=args.kv_layout,
            block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            token_budget=args.token_budget,
            spec_shape=args.spec_shape,
            spec_branch=args.spec_branch,
            fused_kernels=args.fused_kernels,
            kv_dtype=args.kv_dtype,
            slo_aware=getattr(args, "slo_profile", "off") != "off",
            seed=seed if seed is not None else args.seed)


class SpinEngine:
    def __init__(self, llm: sd.Bundle, ssms: Sequence[sd.Bundle],
                 selector, ecfg: EngineConfig,
                 cost_model: Optional[P.CostModel] = None):
        self.llm = llm
        self.ssms = list(ssms)
        self.selector = selector
        self.ecfg = ecfg
        if ecfg.kv_layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {ecfg.kv_layout!r}")
        if ecfg.spec_shape not in ("linear", "tree"):
            raise ValueError(f"unknown spec_shape {ecfg.spec_shape!r}")
        if ecfg.spec_branch < 1:
            raise ValueError("spec_branch must be >= 1")
        if ecfg.fused_kernels not in ("on", "off"):
            raise ValueError(
                f"unknown fused_kernels {ecfg.fused_kernels!r}")
        if ecfg.kv_dtype not in quant.KV_DTYPE_NAMES:
            raise ValueError(
                f"unknown kv_dtype {ecfg.kv_dtype!r} "
                f"(expected one of {'/'.join(quant.KV_DTYPE_NAMES)})")
        if ecfg.gamma_policy == "fixed":
            self.gamma_max = ecfg.gamma
        else:
            self.gamma_max = (ecfg.gamma_max if ecfg.gamma_max is not None
                              else 2 * ecfg.gamma)
        self.paged = (ecfg.kv_layout == "paged"
                      and paged_compatible(llm.cfg)
                      and all(paged_compatible(b.cfg) for b in self.ssms))
        # tree speculation rides the paged packed-verify path (forks are
        # block-table aliases; the topology mask threads through the
        # packed query layout) — anything else falls back to the linear
        # shape, mirroring the paged->dense auto-fallback
        self.tree = (ecfg.spec_shape == "tree" and self.paged
                     and ecfg.use_packed_verify)
        if ecfg.spec_shape == "tree" and not self.tree:
            warnings.warn(
                "spec_shape='tree' requires the paged KV layout and packed "
                "verification; falling back to linear speculation",
                stacklevel=2)
        self.branches = ecfg.spec_branch if self.tree else 1
        max_nodes = D.max_tree_nodes()
        if self.tree and self.gamma_max + min(ecfg.spec_branch,
                                              self.gamma_max) > max_nodes:
            raise ValueError(
                f"tree speculation needs gamma_max + branches <= "
                f"{max_nodes} tree nodes for the "
                f"{D.ANCESTOR_MASK_BITS}-bit ancestor mask (got gamma_max="
                f"{self.gamma_max} + min(spec_branch={ecfg.spec_branch}, "
                f"gamma_max) = "
                f"{self.gamma_max + min(ecfg.spec_branch, self.gamma_max)}"
                f"); lower --gamma-max or --spec-branch")
        # fused Pallas kernels stream KV straight out of the paged block
        # pool, so they require the paged layout.  Every site takes the
        # default tile config: the autotune cache lives in untracked
        # results/ and holds interpret-mode timings, so the served path
        # must not depend on it.  The config is static under jit.
        self.fused = ecfg.fused_kernels == "on" and self.paged
        if ecfg.fused_kernels == "on" and not self.paged:
            warnings.warn(
                "fused_kernels='on' requires the paged KV layout; "
                "falling back to the unfused attention path",
                stacklevel=2)
        self.fused_cfg = autotune.DEFAULT_CONFIG if self.fused else None
        # quantized blocks live in the paged pool's block/scale layout;
        # the dense grids have no sidecar plumbing, so a dense fallback
        # reverts to the compute dtype (mirrors the fused fallback above)
        self.kv_dtype = ecfg.kv_dtype if self.paged else "bf16"
        if quant.is_quantized(ecfg.kv_dtype) and not self.paged:
            warnings.warn(
                f"kv_dtype={ecfg.kv_dtype!r} requires the paged KV "
                "layout; falling back to bf16 (unquantized) KV",
                stacklevel=2)
        # each extra branch needs a pool row to draft/verify through;
        # scheduler capacity (concurrent requests) stays ecfg.capacity
        row_mult = self.branches
        if self.paged:
            bs = ecfg.block_size
            bpr = math.ceil(ecfg.max_len / bs)
            self.max_len = bpr * bs                  # block-aligned
            budget = (ecfg.kv_budget if ecfg.kv_budget is not None
                      else ecfg.capacity * self.max_len)
            # the scheduler enforces the block-rounded budget; the pool
            # holds max(budget, one full row) physical blocks — the extra
            # headroom exists only so an oversized request admitted into
            # an empty pool (deadlock-freedom guarantee) always fits
            budget_blocks = max(1, budget // bs)
            self.llm_pool = PagedCachePool(
                llm.cfg, ecfg.capacity * row_mult, self.max_len, bs,
                num_blocks=max(budget_blocks, bpr),
                kv_dtype=self.kv_dtype)
            # draft pools are capacity-sized (fast switching keeps every
            # row draftable); the budget-constrained pool is the LLM's
            self.ssm_pools = [
                PagedCachePool(b.cfg,
                               selector.cfg.batch_limits[j] * row_mult,
                               self.max_len, bs, kv_dtype=self.kv_dtype)
                for j, b in enumerate(self.ssms)]
            sched_budget = budget_blocks * bs
        else:
            self.max_len = ecfg.max_len
            self.llm_pool = DenseCachePool(llm.cfg, ecfg.capacity,
                                           ecfg.max_len)
            self.ssm_pools = [
                DenseCachePool(b.cfg, selector.cfg.batch_limits[j],
                               ecfg.max_len)
                for j, b in enumerate(self.ssms)]
            sched_budget = ecfg.kv_budget
        self.switcher = SwitchManager(self.ssms)
        self.cost = cost_model or P.CostModel(
            ssm_time_per_token=[1e-4 * (j + 1) for j in range(len(ssms))],
            ssm_fixed=[2e-4] * len(ssms),
            llm_fixed=1e-3, llm_time_per_token=5e-4, gamma=ecfg.gamma)
        if ecfg.replica_class not in ("general", "prefill", "decode"):
            raise ValueError(
                f"unknown replica_class {ecfg.replica_class!r} "
                "(general | prefill | decode)")
        # prefill-class replicas keep adaptive speculation shallow: their
        # verify budget belongs to prompt chunks, and requests routed here
        # are about to be handed off anyway.  Fixed policy ignores the cap
        # (bit-identity contract of --gamma-policy fixed).
        depth_cap = (max(1, math.ceil(self.gamma_max / 2))
                     if ecfg.replica_class == "prefill" else None)
        self.gamma_ctl = GammaController(
            GammaConfig(policy=ecfg.gamma_policy, gamma=ecfg.gamma,
                        gamma_max=self.gamma_max, branches=self.branches,
                        depth_cap=depth_cap),
            self.cost, selector)
        self.failed_ssms: set = set()
        self.requests: Dict[int, Request] = {}
        self.assignment: Dict[int, int] = {}
        # chunked prefill relies on segment-maskable KV appends; recurrent
        # state advances on every token and cannot mask bucket padding, so
        # those models keep monolithic admission (mirrors the paged->dense
        # auto-fallback).
        self.chunked = (ecfg.prefill_chunk > 0
                        and ecfg.scheduler_policy == "continuous"
                        and not llm.has_recurrent_state)
        self.slo_aware = ecfg.slo_aware
        self.scheduler = ContinuousScheduler(SchedulerConfig(
            capacity=ecfg.capacity, max_len=self.max_len,
            gamma=self.gamma_max,
            kv_budget=sched_budget, policy=ecfg.scheduler_policy,
            block_size=ecfg.block_size if self.paged else 0,
            prefill_chunk=ecfg.prefill_chunk if self.chunked else 0,
            token_budget=ecfg.token_budget,
            spec_branches=self.branches,
            slo_aware=ecfg.slo_aware))
        self.rng = jax.random.PRNGKey(ecfg.seed)
        # metrics
        self.sim_time = 0.0
        self.slots = 0                     # simulated slots so far
        self.accepted_tokens = 0
        self.total_drafted = 0
        self.verify_tokens_total = 0       # LLM verify query tokens issued
        self.tree_forks = 0                # CoW row forks (tree mode)
        self.tree_adoptions = 0            # slots won by a non-main branch
        self.reads = 0                     # device->host reads this step
        self.prefill_tokens_total = 0
        self.straggler_redispatches = 0
        # per request: (sum, count) of its per-slot acceptance rates
        self._accept_by_req: Dict[int, tuple] = {}
        # prefill work issued since the last slot simulation (monolithic
        # admissions and chunk appends); consumed into the next slot's
        # makespan so prompt ingestion is paid for on the sim clock
        self._prefill_tokens_pending = 0
        self._prefill_cells_pending = 0.0
        self._unstamped: set = set()       # rids awaiting first_token_time

    # ------------------------------------------------------------ admin --
    @property
    def waiting(self) -> List[Request]:
        """Arrived-but-not-admitted requests (scheduler queue view)."""
        return self.scheduler.waiting

    # ------------------------------------------------- replica-level view --
    # Load/occupancy metrics the multi-replica router (serving/router.py)
    # reads at dispatch time.  Cheap (no JAX work) and deterministic.
    def outstanding_tokens(self) -> int:
        """Token-denominated estimate of all work this engine still owes:
        for every submitted-but-unfinished request, the context still to
        ingest plus the output tokens still to emit."""
        total = 0
        pre = self.scheduler.prefilling
        for r in self.scheduler.outstanding_requests():
            emitted = len(r.emitted or [])
            total += max(0, r.max_new - max(0, emitted - 1))
            if r.rid in pre:
                total += max(0,
                             self.scheduler.prefill_target(r) - r.prefill_pos)
            elif not self.llm_pool.has(r.rid):
                # no row yet: the whole context must still be ingested
                total += self.scheduler.prefill_target(r)
        return total

    def kv_free_cells(self) -> int:
        """*Admissible* KV headroom in cells: the scheduler budget minus
        the running set's projected demand — exactly what admission
        checks.  Under paging this is additionally capped by the
        physical free-block ledger; the pool's one-full-row
        deadlock-freedom floor can hold blocks *above* the budget, and
        that headroom is not admissible, so it must not attract p2c
        dispatches."""
        demand = sum(self.scheduler.kv_need(r)
                     for r in self.scheduler.running.values())
        free = max(0, self.scheduler.kv_budget - demand)
        if self.paged:
            free = min(free,
                       self.llm_pool.free_blocks * self.ecfg.block_size)
        return free

    def kv_occupancy(self) -> float:
        """Fraction of the admissible KV budget currently committed."""
        budget = max(1, self.scheduler.kv_budget)
        return 1.0 - self.kv_free_cells() / budget

    def snapshot(self) -> EngineStats:
        """The engine's typed dispatch-time telemetry: ONE frozen object
        embedding the scheduler snapshot — the router's (and any
        benchmark's) replica view.  ``slo_headroom`` is the SpecServe
        dispatch term: slack to the most urgent outstanding deadline
        minus the estimated time to drain the current token backlog."""
        sched = self.scheduler.snapshot()
        out = self.outstanding_tokens()
        tpt = expected_time_per_token(self.sim_time, self.accepted_tokens,
                                      self.cost.llm_time_per_token)
        return EngineStats(
            sim_time=self.sim_time,
            outstanding_tokens=out,
            kv_free_cells=self.kv_free_cells(),
            kv_occupancy=self.kv_occupancy(),
            accepted_tokens=self.accepted_tokens,
            slo_headroom=slo_headroom(sched.min_deadline, self.sim_time,
                                      out, tpt),
            scheduler=sched)

    def add_requests(self, reqs: Sequence[Request]):
        """Submit requests.  Arrival timestamps on the requests are
        honoured: a request whose ``arrival`` lies in the simulated future
        stays pending until the engine clock reaches it."""
        for r in reqs:
            # worst-case KV cells this request can ever occupy: full
            # context + speculation window.  Validating here keeps every
            # later (re-)prefill in bounds — a silent out-of-range scatter
            # would corrupt the cache instead of erroring.
            need = r.prompt_len + r.max_new + self.gamma_max + 1
            if need > self.max_len:
                raise ValueError(
                    f"request {r.rid} needs up to {need} KV slots "
                    f"(prompt {r.prompt_len} + max_new {r.max_new} + "
                    f"gamma_max+1) > max_len={self.max_len}")
        self.scheduler.submit(reqs)
        self._schedule()

    def release_queued(self, rids: Optional[Sequence[int]] = None, *,
                       include_pending: bool = False) -> List[Request]:
        """Hand queued (rowless) requests off to another replica — the
        work-stealing / drain release hook.  Only waiting requests (and,
        with ``include_pending``, not-yet-arrived ones — the drain case)
        leave; row owners keep decoding here.  A released request holds
        no pool row and therefore no KV on this engine — the target
        re-prefills its context from the ``Request`` itself, so there is
        no stale cache to migrate or corrupt.  The rid is scrubbed from
        every engine-side index so fleet-level stats (which union
        ``requests`` across replicas) count it exactly once, at whichever
        replica finishes it."""
        out = self.scheduler.release_queued(rids,
                                            include_pending=include_pending)
        for r in out:
            assert not self.llm_pool.has(r.rid), \
                f"released request {r.rid} still owns a KV row"
            self.requests.pop(r.rid, None)
            self._unstamped.discard(r.rid)
            self._accept_by_req.pop(r.rid, None)
        return out

    def _schedule(self, grant_prefill: bool = False):
        """Ask the scheduler for this instant's decision and apply it:
        preemptions release rows/KV first, then admissions take rows, then
        prefill chunks are appended.  ``grant_prefill`` is True only for
        the start-of-step pass so the chunk budget is spent once per slot
        (end-of-step recycling and ``add_requests`` only move rows)."""
        with span("spin.schedule"):
            dec = self.scheduler.plan(self.sim_time,
                                      grant_prefill=grant_prefill)
            for r in dec.preempt:
                self._preempt(r)
            for r in dec.admit:
                if r.first_token_time is None:
                    self._unstamped.add(r.rid)
                self._begin_admit(r)
            for r, n in dec.prefill:
                self._prefill_chunk(r, n)

    @staticmethod
    def _context_tokens(r: Request) -> np.ndarray:
        """Committed context to (re-)prefill: the prompt plus emitted
        tokens except the last, which has not been fed back yet — it
        becomes the pool's last_token."""
        return np.concatenate([np.asarray(r.prompt, np.int64),
                               np.asarray(r.emitted[:-1] if r.emitted
                                          else [], np.int64)])

    @staticmethod
    def _admit_meta(r: Request):
        return lambda: {"rid": r.rid,
                        "context": r.prompt_len + max(0, len(r.emitted or [])
                                                      - 1)}

    def _begin_admit(self, r: Request):
        """Grant the request a pool row.  Monolithic mode prefills the
        whole context here (fresh prompt, or prompt + committed tokens
        after preemption — greedy continuation stays bit-identical to an
        uninterrupted run).  Chunked mode only takes the row; context
        arrives through :meth:`_prefill_chunk` grants.  The first
        admission stamps ``host_admitted``."""
        with span("spin.admit", self._admit_meta(r)):
            if r.host_admitted is None:
                r.host_admitted = time.perf_counter()
            self.requests[r.rid] = r
            if self.chunked:
                r.prefill_pos = 0
                self.llm_pool.insert_empty(r.rid)
                self.scheduler.mark_admitted(r, self.sim_time)
                return
            tokens = self._context_tokens(r)
            L = len(tokens)
            row = np.zeros((1, _bucket(L)), np.int32)
            row[0, :L] = tokens
            lengths = jnp.asarray([L], jnp.int32)
            # paged: prefill a cache of just the prompt's blocks —
            # admission cost is O(prompt blocks), independent of pool
            # capacity/max_len
            plen = (self.llm_pool.prefill_len(row.shape[1]) if self.paged
                    else self.max_len)
            with span("spin.prefill", lambda: {"rid": r.rid, "tokens": L}):
                logits, cache = self.llm.prefill(jnp.asarray(row), lengths,
                                                 plen)
                last = self._first_token(r, logits, L - 1)
            self.llm_pool.insert(r.rid, cache, L, last)
            self._account_prefill(0, L)
            self.scheduler.mark_admitted(r, self.sim_time)

    def _first_token(self, r: Request, logits, idx: int) -> int:
        """The token that follows the ingested context — the emitted tail
        on re-admission, else the greedy pick at the last context
        position.  Shared by the monolithic and final-chunk paths so the
        bit-exactness contract between them cannot drift."""
        if r.emitted:
            return int(r.emitted[-1])
        last = int(self._read(
            jnp.argmax(logits[0, idx, :self.llm.cfg.vocab_size])))
        r.emitted = [last]
        if r.host_first_token is None:
            r.host_first_token = time.perf_counter()
        return last

    def _account_prefill(self, pos: int, n: int):
        """Record prefill work for the next slot simulation: n query
        tokens starting at context offset pos, attending Σ (pos+i+1)
        KV cells — same affine terms as verification."""
        self._prefill_tokens_pending += n
        self._prefill_cells_pending += n * pos + n * (n + 1) / 2.0

    def _prefill_chunk(self, r: Request, n: int):
        """Append one prompt chunk into the request's existing row.  The
        chunk's queries attend the prior context plus themselves causally
        (decode-path forward), so the final logits — and therefore the
        first emitted token and the greedy continuation — are the
        monolithic prefill's.  Bucket padding carries segment -1: its KV
        writes land invalidated and one trace serves each width bucket."""
        rid = r.rid
        ctx = self._context_tokens(r)
        L = len(ctx)
        pos = r.prefill_pos
        n = min(n, L - pos)
        if n <= 0:
            return
        with span("spin.admit", self._admit_meta(r)):
            Tb = _bucket(n, 8)
            toks = np.zeros((1, Tb), np.int32)
            toks[0, :n] = ctx[pos:pos + n]
            segs = np.full((1, Tb), -1, np.int32)
            segs[0, :n] = 0
            lengths = jnp.asarray([pos], jnp.int32)
            with span("spin.prefill", lambda: {"rid": rid, "tokens": n}):
                if self.paged:
                    self.llm_pool.ensure(rid, pos + n)
                    bt = self.llm_pool.row_table(rid)
                    logits, cache = self.llm.append_paged(
                        self.llm_pool.cache, jnp.asarray(toks), lengths,
                        jnp.asarray(segs), bt, self.fused_cfg)
                    self.llm_pool.cache = cache
                else:
                    one = self.llm_pool.row_cache(rid)
                    logits, one = self.llm.append(
                        one, jnp.asarray(toks), lengths, jnp.asarray(segs))
                    self.llm_pool.write_row(rid, one)
                first = (self._first_token(r, logits, n - 1)
                         if pos + n >= L else None)
            r.prefill_pos = pos + n
            row = self.llm_pool.row_of[rid]
            self.llm_pool.lengths[row] = r.prefill_pos
            self._account_prefill(pos, n)
            if first is not None:
                self.llm_pool.last_token[row] = first
                self.scheduler.mark_prefill_done(r)

    def _preempt(self, r: Request):
        """Release the request's row and draft-pool slot; generated tokens
        stay on the Request, so nothing decoded is lost."""
        rid = r.rid
        if self.llm_pool.has(rid):
            self.llm_pool.evict(rid)
        j = self.assignment.pop(rid, None)
        if j is not None and self.ssm_pools[j].has(rid):
            self.ssm_pools[j].evict(rid)
        if hasattr(self.selector, "retire"):
            self.selector.retire(rid)
        self.gamma_ctl.retire(rid)
        self.scheduler.mark_preempted(r, self.sim_time)

    def _finish(self, r: Request):
        r.done = True
        r.finish_time = self.sim_time
        self.llm_pool.evict(r.rid)
        j = self.assignment.pop(r.rid, None)
        if j is not None and self.ssm_pools[j].has(r.rid):
            self.ssm_pools[j].evict(r.rid)
        if hasattr(self.selector, "retire"):
            self.selector.retire(r.rid)
        self.gamma_ctl.retire(r.rid)
        self.scheduler.mark_finished(r.rid)

    def fail_ssm(self, j: int):
        """Replica failure: drain its requests, zero its capacity."""
        self.failed_ssms.add(j)
        self.selector.cfg.batch_limits[j] = 0
        for rid in list(self.ssm_pools[j].row_of):
            self.ssm_pools[j].evict(rid)
            self.assignment.pop(rid, None)

    # --------------------------------------------------------- one slot --
    def _active(self) -> List[Request]:
        """Decode-ready requests: own a row AND are fully prefilled —
        prefilling rows hold partial KV and must not draft or verify."""
        pre = self.scheduler.prefilling
        return [r for r in self.requests.values()
                if not r.done and self.llm_pool.has(r.rid)
                and r.rid not in pre]

    def _consume_prefill(self):
        """(time, tokens) of prefill work issued since the last slot
        simulation; resets the pending counters."""
        toks = self._prefill_tokens_pending
        t = self.cost.prefill_time(toks, self._prefill_cells_pending)
        self.prefill_tokens_total += toks
        self._prefill_tokens_pending = 0
        self._prefill_cells_pending = 0.0
        return t, toks

    def _stamp_tokens(self, r: Request):
        """Deadline attainment source: ``token_times[j]`` is the sim-clock
        instant token j was committed — the end of the slot that paid for
        it (commit loop) or, for the prefill-born first token, the end of
        the slot that carried the prefill work (same instant
        ``first_token_time`` is stamped).  Idempotent: only missing tails
        are appended, so preempted requests keep their history."""
        if r.token_times is None:
            r.token_times = []
        while len(r.token_times) < len(r.emitted or []):
            r.token_times.append(self.sim_time)

    def _stamp_first_tokens(self):
        """TTFT: a request's first token exists once its (monolithic or
        final-chunk) prefill has been paid for on the sim clock — i.e. at
        the end of the slot that carried the work.  Only requests not yet
        stamped are scanned, so the per-slot cost tracks new first tokens
        rather than total stream history."""
        for rid in list(self._unstamped):
            r = self.requests[rid]
            if r.emitted:
                r.first_token_time = self.sim_time
                self._stamp_tokens(r)
                self._unstamped.discard(rid)

    def kv_cells(self):
        """(used, held, alloc) KV cells summed over the target pool and
        every drafter pool (see ``PagedCachePool.cells``)."""
        used = held = alloc = 0
        for pool in [self.llm_pool, *self.ssm_pools]:
            u, h, a = pool.cells()
            used, held, alloc = used + u, held + h, alloc + a
        return used, held, alloc

    def _step_meta(self, rec: dict) -> dict:
        used, held, alloc = self.kv_cells()
        return {"rows": rec.get("active", 0),
                "reads": self.reads,
                "waiting": len(self.scheduler.waiting),
                "kv_used": used, "kv_held": held, "kv_alloc": alloc}

    def step(self) -> dict:
        """One slot (module docstring), inside the ``spin.step`` span;
        ``docs/SERVING.md`` lists the span tree."""
        self.reads = 0
        with span("spin.step") as s:
            rec = self._step()
            annotate(s, lambda: self._step_meta(rec))
        return rec

    def _read(self, x):
        """Copy the device array (or tuple of arrays) ``x`` to the host.
        Every read the engine makes goes through here and is counted in
        ``reads``: a step reads each drafter's candidates and verify's
        results once, plus one first token per finished prefill."""
        self.reads += 1
        return jax.device_get(x)

    def _step(self) -> dict:
        self._schedule(grant_prefill=True)
        active = self._active()
        if not active:
            nxt = self.scheduler.next_arrival()
            if nxt is not None and not self.scheduler.running:
                # pool drained: fast-forward the sim clock to the next
                # arrival and admit it
                self.sim_time = max(self.sim_time, nxt)
                self._schedule(grant_prefill=True)
                active = self._active()
        if not active:
            if self._prefill_tokens_pending > 0:
                # prefill-only slot: every row is still ingesting context;
                # the clock advances by the chunk work just issued
                with span("spin.commit"):
                    pre_t, pre_n = self._consume_prefill()
                    self.sim_time += pre_t
                    self._stamp_first_tokens()
                self.slots += 1
                return {"tokens": 0, "sim_time": pre_t, "llm_idle": 0.0,
                        "micro_batches": [], "active": 0,
                        "running": len(self.scheduler.running),
                        "queued": len(self.scheduler.waiting),
                        "prefill_tokens": pre_n}
            return {"done": True}
        ids = [r.rid for r in active]
        with span("spin.assign") as s:
            assign = self.selector.assign(ids)
            # apply switches / placements
            switches = 0
            for rid, j in assign.items():
                if j in self.failed_ssms:
                    j = min(set(range(len(self.ssms))) - self.failed_ssms)
                    assign[rid] = j
                prev = self.assignment.get(rid)
                if prev == j and self.ssm_pools[j].has(rid):
                    continue
                if prev is not None and prev != j and \
                        self.ssm_pools[prev].has(rid):
                    self.ssm_pools[prev].evict(rid)
                if not self.ssm_pools[j].has(rid):
                    self._place_on_ssm(rid, j, assign)
                    switches += 1
                self.assignment[rid] = j
            annotate(s, lambda: {"switches": switches})
        with span("spin.draft"):
            drafts, depths, per_ssm = self._draft(active, ids, assign)
        self.total_drafted += sum(depths.values())
        self.verify_tokens_total += sum(
            depths[rid] + self._beff(depths[rid]) for rid in ids)

        # verification (functional, full batch; per-row depth masking)
        n_acc, out, out_len = self._verify(ids, drafts, depths)

        with span("spin.commit"):
            slot_tokens, mb, slot, pre_n = self._commit(
                ids, assign, depths, n_acc, out, out_len, per_ssm)
        self.slots += 1

        # fast-switching prediction for next slot (§IV-C)
        with span("spin.precompute"):
            self._precompute_switches(ids)
        # recycle rows freed by finished requests within the SAME step:
        # queued arrivals are admitted into them before the slot returns
        self._schedule()

        return {"tokens": slot_tokens, "sim_time": slot.makespan,
                "llm_idle": slot.llm_idle_frac, "micro_batches": mb,
                "active": len(ids),
                "running": len(self.scheduler.running),
                "queued": len(self.scheduler.waiting),
                "prefill_tokens": pre_n}

    def _draft(self, active, ids, assign):
        """Grant each request its depth and draft on every SSM pool:
        returns (drafts, depths, per-SSM costs), the costs being the
        per-SSM batch, mean depth and mean extra verify tokens the slot
        simulation charges."""
        # per-request speculation depths for this slot (goodput-aware
        # argmax on the selector's acceptance estimates; "fixed" policy:
        # the uniform ecfg.gamma).  The cap charges the prompt-chunk
        # tokens this slot's plan already granted, so decode + prefill
        # together respect the token budget; the scheduler's next
        # token-budget split costs decode slots at these granted depths.
        slo_slack = None
        if self.slo_aware:
            # seconds until each SLO-carrying request's next-token
            # deadline — the gamma controller's deadline-headroom input;
            # None/absent entries mean no deadline pressure
            slo_slack = {r.rid: r.next_deadline() - self.sim_time
                         for r in active if r.slo is not None} or None
        depths = self.gamma_ctl.grant(
            ids, assign,
            token_budget=self.ecfg.token_budget if self.chunked else None,
            reserved_tokens=self.scheduler.last_prefill_granted,
            slo_slack=slo_slack)
        # tree mode: a depth-k grant verifies k + b_eff query tokens (one
        # root copy per branch), so the step planner's token-budget split
        # must see that cost; linear b_eff = 1 keeps the k + 1 charge
        self.scheduler.set_decode_depths(
            {rid: k + self._beff(k) - 1 for rid, k in depths.items()}
            if self.tree else depths)
        if self.paged:
            # append-a-block growth: cover context + this slot's granted
            # speculation window (k_i + 1) before decode/verify writes land
            self.llm_pool.ensure_rows({
                r.rid: int(self.llm_pool.lengths[self.llm_pool.row_of[r.rid]])
                + depths[r.rid] + 1 for r in active})

        # draft on every SSM pool (static shapes at the pool's slot-max
        # depth; rows granted less contribute only their k_i-token prefix)
        drafts: Dict[int, object] = {}
        per_ssm_batch = []
        per_ssm_depth = []
        per_ssm_vextra = []
        for j, (b, pool) in enumerate(zip(self.ssms, self.ssm_pools)):
            rids = [r for r in ids if assign.get(r) == j]
            per_ssm_batch.append(len(rids))
            if not rids or j in self.failed_ssms:
                per_ssm_depth.append(float(self.cost.gamma))
                per_ssm_vextra.append(0.0)
                continue
            # ragged per-slot batch: cost covers the requests actually
            # assigned this slot at their granted depths, not the static
            # pool capacity at a uniform gamma
            per_ssm_depth.append(float(np.mean([depths[r] for r in rids])))
            per_ssm_vextra.append(float(np.mean(
                [self._beff(depths[r]) - 1 for r in rids])))
            width = max(depths[r] for r in rids)
            with span("spin.draft", lambda: {"ssm": j, "width": width}):
                if self.tree:
                    cand, branch_map = self._draft_pool_tree(
                        j, width, depths, rids)
                else:
                    cand = self._draft_pool(j, width, depths)
            if self.tree:
                for rid in rids:
                    drafts[rid] = [cand[row, :kk]
                                   for row, kk in branch_map[rid]]
            else:
                rows = pool.rows(rids)
                for rid, row in zip(rids, rows):
                    drafts[rid] = cand[row, :depths[rid]]
        return drafts, depths, (per_ssm_batch, per_ssm_depth, per_ssm_vextra)

    def _commit(self, ids, assign, depths, n_acc, out, out_len, per_ssm):
        """Simulate the slot on the sim clock and commit its tokens:
        returns (tokens committed, micro-batches, the slot's
        ``SimResult``, prefill tokens it carried)."""
        per_ssm_batch, per_ssm_depth, per_ssm_vextra = per_ssm
        # simulated slot timeline (pipeline §V-B); verification cost sees
        # the padded vs decomposed-packed KV grid size (§V-A), ragged per
        # SSM under continuous batching — and ragged draft depths under
        # the adaptive gamma policy
        accept_rates = self._accept_rates_per_ssm(assign, ids, n_acc, depths)
        kv_cells_per_req = self._kv_cells_per_ssm(assign, ids, depths)
        vextra = per_ssm_vextra if self.tree else None
        if self.ecfg.use_pipeline:
            mb = self.ecfg.micro_batches or P.choose_micro_batches(
                self.cost, per_ssm_batch, accept_rates,
                kv_cells_per_req=kv_cells_per_req,
                depth_per_req=per_ssm_depth,
                verify_extra_per_req=vextra)[0]
        else:
            mb = [1] * len(self.ssms)
        # mixed slot: chunk-prefill work issued this step (and monolithic
        # admissions since the last slot) occupies the LLM ahead of the
        # verify queue while SSMs draft concurrently
        pre_t, pre_n = self._consume_prefill()
        slot = self._simulate_slot(per_ssm_batch, mb, kv_cells_per_req,
                                   prefill_time=pre_t,
                                   depth_per_req=per_ssm_depth,
                                   verify_extra_per_req=vextra)

        # commit tokens, update request state, observe goodput + acceptance
        self.sim_time += slot.makespan
        slot_tokens = 0
        observe_accept = getattr(self.selector, "observe_accept", None)
        for i, rid in enumerate(ids):
            r = self.requests[rid]
            k = int(out_len[i])
            r.emitted.extend(int(x) for x in out[i, :k])
            self._stamp_tokens(r)
            slot_tokens += k
            g = k / max(slot.makespan, 1e-9)
            self.selector.observe(rid, assign[rid], g)
            # per-token acceptance estimate: successes over positions
            # actually tested — the accept chain stops at the first
            # rejection, so n_acc/k would bias deep grants low (a
            # truncated-geometric mean) and collapse adaptive depths
            tested = min(depths[rid], int(n_acc[i]) + 1)
            rate = float(n_acc[i]) / tested
            if observe_accept is not None:
                observe_accept(rid, assign[rid], rate)
            total, n = self._accept_by_req.get(rid, (0.0, 0))
            self._accept_by_req[rid] = (total + rate, n + 1)
            if len(r.emitted) - 1 >= r.max_new:
                self._finish(r)
        self.accepted_tokens += slot_tokens
        self._stamp_first_tokens()
        return slot_tokens, mb, slot, pre_n

    # ---------------------------------------------------------- internals --
    def _switch_width(self, j: int, length: int) -> int:
        """Cache width for switch prefills/precomputes on SSM j.  Paged
        pools only need the context's blocks (plus a gamma_max+1 growth
        margin so a next-slot switch still hits at any granted depth) —
        O(context), not the capacity-proportional max_len the dense layout
        requires."""
        if not self.paged:
            return self.max_len
        need = min(self.max_len, length + self.gamma_max + 1)
        return self.ssm_pools[j].prefill_len(_bucket(need))

    def _place_on_ssm(self, rid: int, j: int, current):
        """Switch-place ``rid`` on SSM j's pool.  ``current`` is this
        slot's full assignment map: residents NOT placed here this slot
        are the eviction candidates (a resident may still carry a stale
        ``self.assignment`` entry while it moves away later in the same
        placement pass)."""
        r = self.requests[rid]
        tokens = np.concatenate([np.asarray(r.prompt),
                                 np.asarray(r.emitted[:-1], np.int64)])
        length = len(tokens)
        cache, _ = self.switcher.switch(rid, j, tokens, length,
                                        self._switch_width(j, length))
        pool = self.ssm_pools[j]
        while not pool.can_admit(length):
            # evict someone not assigned here this slot (frees the row
            # and, under paging, its blocks)
            victim = next((rr for rr in pool.row_of
                           if current.get(rr) != j), None)
            if victim is None:
                raise RuntimeError(
                    f"SSM {j} draft pool over-committed: all "
                    f"{len(pool.row_of)} residents are assigned here this "
                    f"slot — selector batch_limits[{j}] exceeds the pool")
            pool.evict(victim)
        pool.insert(rid, cache, length, r.emitted[-1])

    def _precompute_switches(self, ids):
        if not hasattr(self.selector, "predicted_destination"):
            return
        for rid in ids:
            if rid not in self.requests or self.requests[rid].done:
                continue
            dst = self.selector.predicted_destination(rid)
            if dst == self.assignment.get(rid) or dst in self.failed_ssms:
                continue
            r = self.requests[rid]
            tokens = np.concatenate([np.asarray(r.prompt),
                                     np.asarray(r.emitted[:-1], np.int64)])
            self.switcher.precompute(rid, dst, tokens, len(tokens),
                                     self._switch_width(dst, len(tokens)))

    def _draft_pool(self, j: int, width: int, depths) -> np.ndarray:
        """Draft ``width`` tokens (the slot-max granted depth on this SSM)
        for every row of SSM j's pool; returns (capacity, width)
        candidates — callers take each row's granted k_i-prefix.  Inactive
        rows are drafted too (static shape); dense rows are re-invalidated
        afterwards, paged idle rows own no blocks so their writes are
        dropped at the source."""
        b = self.ssms[j]
        pool = self.ssm_pools[j]
        lengths = jnp.asarray(pool.lengths, jnp.int32)
        tok = jnp.asarray(pool.last_token, jnp.int32)[:, None]
        self.rng, k = jax.random.split(self.rng)
        if self.paged:
            # cover draft writes (ctx..ctx+k_i-1) and the catch-up hole
            # fill (ctx+1..ctx+k_i+1) before any decode lands
            pool.ensure_rows({
                rid: int(pool.lengths[row]) + depths.get(rid, width) + 2
                for rid, row in pool.row_of.items()})
            bt, _ = pool.block_table_array()
            cand, _, cache = sd.draft(b, pool.cache, tok, lengths,
                                      width, k, block_tables=bt,
                                      fused_cfg=self.fused_cfg)
            pool.cache = cache
            return self._read(cand)
        cand, _, cache = sd.draft(b, pool.cache, tok, lengths,
                                  width, k)
        pool.cache = cache
        idle = [row for row in range(pool.capacity)
                if row not in pool.row_of.values()]
        pool.invalidate_rows(idle)
        return self._read(cand)

    # ----------------------------------------------------- tree helpers --
    @staticmethod
    def _brid(rid: int, j: int):
        """Synthetic pool key for branch j of request rid — tuples never
        collide with real (integer) request ids."""
        return ("~branch", rid, j)

    def _beff(self, k) -> int:
        """Effective branch count of a depth-k grant: every branch drafts
        at least one token, so min(branches, k); 1 in linear mode."""
        return max(1, min(self.branches, int(k))) if self.tree else 1

    def _draft_pool_tree(self, j: int, width: int, depths, rids):
        """Tree drafting on SSM j: fork a CoW pool row per extra branch,
        draft every row greedily with per-row first-step top-k ranks
        (identical context in forked rows means identical step-1 logits,
        so each row self-selects its branch without cross-row
        communication), then evict the fork rows — their chains live on
        as verify candidates, and accepted tokens re-enter the main row
        via the catch-up decode.  Returns (cand (capacity, width),
        branch_map: rid -> [(row, k_j), ...] branch-ordered)."""
        b = self.ssms[j]
        pool = self.ssm_pools[j]
        # cover draft writes + catch-up hole on the resident (main) rows
        pool.ensure_rows({
            rid: int(pool.lengths[row]) + depths.get(rid, width) + 2
            for rid, row in pool.row_of.items()})
        # stale switch residents may hold rows the forks need: the pool
        # has batch_limits * branches rows, so evicting non-assigned
        # residents always frees enough
        need = sum(self._beff(depths[rid]) - 1 for rid in rids)
        free = pool.capacity - len(pool.row_of)
        if free < need:
            keep = set(rids)
            for victim in [r for r in pool.row_of if r not in keep]:
                pool.evict(victim)
                free += 1
                if free >= need:
                    break
        branch_map = {}
        forked = []
        for rid in rids:
            bd = D.split_tree_depths(depths[rid], self.branches)
            L = int(pool.lengths[pool.row_of[rid]])
            entries = [(pool.row_of[rid], bd[0])]
            for jj in range(1, len(bd)):
                brid = self._brid(rid, jj)
                entries.append((pool.fork(rid, brid), bd[jj]))
                forked.append(brid)
            if len(bd) > 1:
                for jj in range(1, len(bd)):
                    pool.cow_prepare(self._brid(rid, jj), L, L + width + 2)
                pool.cow_prepare(rid, L, L + width + 2)
            branch_map[rid] = entries
        ranks = np.zeros(pool.capacity, np.int32)
        for rid in rids:
            for bi, (row, _) in enumerate(branch_map[rid]):
                ranks[row] = bi
        lengths = jnp.asarray(pool.lengths, jnp.int32)
        tok = jnp.asarray(pool.last_token, jnp.int32)[:, None]
        # keep the rng stream aligned with the linear draft path
        self.rng, _ = jax.random.split(self.rng)
        bt, _ = pool.block_table_array()
        cand, cache = sd.draft_tree(b, pool.cache, tok, lengths, width,
                                    ranks, block_tables=bt,
                                    fused_cfg=self.fused_cfg)
        pool.cache = cache
        for brid in forked:
            pool.evict(brid)
        return self._read(cand), branch_map

    def _tree_block_maps(self, ids_np, owner_np, tree_rows, W: int):
        """Per-slot tree metadata for the packed gather: block owners of
        branch rows remap to the request's main row (the verify segment),
        and every gathered KV slot gets a tree-node tag — -1 committed
        (attendable via segment + causality alone), -2 dead (a branch's
        CoW copy of committed straddle cells, which would otherwise be
        softmax-counted twice, or a padding slot past the branch's
        depth), n >= 0 a tree node attendable only by queries whose
        ancestor bitmask has bit n set."""
        pool = self.llm_pool
        bs = pool.block_size
        seg_of_row = {row: seg for row, (seg, _, _) in tree_rows.items()}
        owner_seg = np.array(
            [seg_of_row.get(int(o), int(o)) if o >= 0 else -1
             for o in owner_np], np.int32)
        id2m = {int(blk): m for m, blk in enumerate(ids_np)
                if owner_np[m] >= 0}
        node = np.full((len(ids_np), bs), -1, np.int32)
        for row, (seg_row, off, k) in tree_rows.items():
            L = int(pool.lengths[row])
            nb = int(pool._nb[row])
            if row != seg_row and L % bs:
                # branch rows own a private copy of the straddling tail
                # block; its committed cells [L - L%bs, L) duplicate the
                # main row's originals — dead-tag the copies
                bi0 = L // bs
                if bi0 < nb:
                    m = id2m.get(int(pool._table[row, bi0]))
                    if m is not None:
                        node[m, :L % bs] = -2
            for d in range(W + 1):
                p = L + d
                bi = p // bs
                if bi >= nb:
                    break        # writes past the table were dropped
                m = id2m.get(int(pool._table[row, bi]))
                if m is None:
                    continue
                node[m, p % bs] = (off + d) if d <= k else -2
        return owner_seg, node

    def _verify(self, ids, drafts, depths):
        """LLM verification over the full pool (padded or packed).

        ``depths`` maps request -> granted speculation depth.  The forward
        runs at the slot's max depth W (static shape per W; at most
        gamma_max distinct traces); rows granted less carry zero-padded
        candidate tails whose match is masked out, so a row can never
        accept beyond its grant, and whose speculative KV writes land in
        the rollback scrub window like any rejected draft."""
        W = max(depths[rid] for rid in ids)
        with span("spin.verify", lambda: {"width": W}):
            N = self.llm_pool.capacity
            # tree mode: fork a CoW row per extra branch BEFORE capturing the
            # pool arrays — each branch verifies its own root copy + chain
            # through its own (prefix-shared) block table
            fork_rows: Dict[int, list] = {}
            tree_rows = None
            if self.tree:
                tree_rows = {}
                for rid in ids:
                    bd = D.split_tree_depths(depths[rid], self.branches)
                    mrow = self.llm_pool.row_of[rid]
                    L = int(self.llm_pool.lengths[mrow])
                    lst = []
                    for jj in range(1, len(bd)):
                        brid = self._brid(rid, jj)
                        brow = self.llm_pool.fork(rid, brid)
                        lst.append((jj, brid, brow))
                        self.tree_forks += 1
                    if lst:
                        # un-share the speculation window: every branch (and
                        # the main row, last so it keeps the originals) writes
                        # through private block copies
                        for jj, brid, brow in lst:
                            self.llm_pool.cow_prepare(brid, L, L + W + 2)
                        self.llm_pool.cow_prepare(rid, L, L + W + 2)
                    fork_rows[rid] = lst
                    tree_rows[mrow] = (mrow, 0, bd[0])
                    off = bd[0] + 1
                    for jj, brid, brow in lst:
                        tree_rows[brow] = (mrow, off, bd[jj])
                        off += bd[jj] + 1
            cand = np.zeros((N, W), np.int32)
            k_row = np.zeros(N, np.int64)
            lengths = jnp.asarray(self.llm_pool.lengths, jnp.int32)
            last = jnp.asarray(self.llm_pool.last_token, jnp.int32)[:, None]
            rows = self.llm_pool.rows(ids)
            for rid, row in zip(ids, rows):
                if self.tree:
                    bd = D.split_tree_depths(depths[rid], self.branches)
                    chains = drafts.get(
                        rid, [np.zeros(kk, np.int32) for kk in bd])
                    cand[row, :len(chains[0])] = chains[0]
                    k_row[row] = bd[0]
                    for (jj, brid, brow) in fork_rows[rid]:
                        cand[brow, :len(chains[jj])] = chains[jj]
                        k_row[brow] = bd[jj]
                else:
                    d = drafts.get(rid, np.zeros(depths[rid], np.int32))
                    cand[row, :len(d)] = d
                    k_row[row] = depths[rid]
            cand = jnp.asarray(cand)

            if self.ecfg.use_packed_verify:
                logits = self._verify_packed(cand, last, W,
                                             tree_rows=tree_rows)
            else:
                inp = jnp.concatenate([last, cand], axis=1)
                if self.paged:
                    bt, _ = self.llm_pool.block_table_array()
                    logits, cache = self.llm.decode_paged(
                        self.llm_pool.cache, inp, lengths, bt,
                        self.fused_cfg)
                else:
                    logits, cache = self.llm.decode(self.llm_pool.cache, inp,
                                                    lengths)
                self.llm_pool.cache = cache
            V = self.llm.cfg.vocab_size
            greedy = jnp.argmax(logits.astype(jnp.float32)[..., :V],
                                axis=-1).astype(jnp.int32)
            # per-row depth mask: positions at or beyond a row's grant can
            # never match (they hold padding, not drafts)
            in_depth = (jnp.arange(W)[None]
                        < jnp.asarray(k_row, jnp.int32)[:, None])
            match = (greedy[:, :W] == cand) & in_depth
            n_acc_all = jnp.sum(jnp.cumprod(match.astype(jnp.int32), 1), 1)
            idx = jnp.arange(W + 1)[None]
            out_all = jnp.where(idx < n_acc_all[:, None],
                                jnp.pad(cand, ((0, 0), (0, 1))), 0)
            bonus = jnp.take_along_axis(greedy, n_acc_all[:, None], axis=1)
            out_all = out_all.at[jnp.arange(N), n_acc_all].set(bonus[:, 0])

            # tree: adopt the winning branch per request — the row with the
            # longest accepted root-to-leaf path keeps the request id (its CoW
            # copies become canonical); losers are evicted in O(branches),
            # dropping refs so shared prefix blocks survive via the winner.
            # Under greedy verification at most one branch accepts >= 1 token
            # (branches differ at their first draft and only the one matching
            # the LLM argmax can accept), so ties land on branch 0 and the
            # bonus token is the LLM's own pick — lossless at any shape.
            winner_row = {rid: row for rid, row in zip(ids, rows)}
            host = None
            if self.tree:
                host = self._read((out_all, n_acc_all))
                _, n_acc_np = host
                for rid in ids:
                    best_j, best_row = 0, winner_row[rid]
                    for (jj, brid, brow) in fork_rows[rid]:
                        if int(n_acc_np[brow]) > int(n_acc_np[best_row]):
                            best_j, best_row = jj, brow
                    if best_j != 0:
                        self.llm_pool.evict(rid)
                        self.llm_pool.rename(self._brid(rid, best_j), rid)
                        self.tree_adoptions += 1
                    for (jj, brid, brow) in fork_rows[rid]:
                        if jj != best_j:
                            self.llm_pool.evict(brid)
                    winner_row[rid] = best_row

        with span("spin.rollback"):
            # rollback: keep accepted prefix only (paged: trim the tail block
            # in place — a W-wide seg scatter through the block table)
            if self.paged:
                self.llm_pool.invalidate_span(lengths + 1 + n_acc_all,
                                              lengths + W + 1, W=W)
            else:
                self.llm_pool.cache = sd.invalidate_slots_jit(
                    self.llm_pool.cache, lengths + 1 + n_acc_all,
                    lengths + W + 1)
                self.llm_pool.invalidate_rows(
                    [row for row in range(N)
                     if row not in self.llm_pool.row_of.values()])
            # prefilling rows are live pool rows but take no part in this
            # verify: the full-pool forward still wrote speculative KV at
            # their positions [len, len+W+1) — scrub all of it, or a later
            # chunk landing below those positions would leave stale
            # attendable garbage beyond the context
            pre_rows = [self.llm_pool.row_of[rid]
                        for rid in self.scheduler.prefilling
                        if rid in self.llm_pool.row_of]
            if pre_rows:
                lo = np.zeros(N, np.int64)
                hi = np.zeros(N, np.int64)
                lens_now = np.asarray(self.llm_pool.lengths, np.int64)
                for row in pre_rows:
                    lo[row] = lens_now[row]
                    hi[row] = lens_now[row] + W + 1
                if self.paged:
                    self.llm_pool.invalidate_span(
                        jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                        W=W + 1)
                else:
                    self.llm_pool.cache = sd.invalidate_slots_jit(
                        self.llm_pool.cache, jnp.asarray(lo, jnp.int32),
                        jnp.asarray(hi, jnp.int32))

        with span("spin.catchup"):
            # per-SSM catch-up (fill the c_k hole) + rollback on draft
            # pools; the inputs are gathered from verify's results on the
            # device, so the catch-up queues behind verify with no host
            # round trip
            for j, pool in enumerate(self.ssm_pools):
                if not pool.row_of:
                    continue
                pl = jnp.asarray(pool.lengths, jnp.int32)
                outs_j, nacc_j = catchup_inputs(
                    out_all, n_acc_all, jnp.asarray(catchup_rows(
                        pool.row_of, self.llm_pool.row_of, pool.capacity)))
                if self.paged:
                    bt, _ = pool.block_table_array()
                    _, pool.cache = self.ssms[j].decode_paged(
                        pool.cache, outs_j, pl + 1, bt, self.fused_cfg)
                    pool.invalidate_span(pl + 2 + nacc_j, pl + W + 3,
                                         W=W + 1)
                else:
                    _, pool.cache = self.ssms[j].decode(
                        pool.cache, outs_j, pl + 1)
                    pool.cache = sd.invalidate_slots_jit(
                        pool.cache, pl + 2 + nacc_j, pl + W + 3)
            # verify's results reach the host once a step, while the
            # catch-up runs (tree mode read them already, to adopt)
            if host is None:
                host = self._read((out_all, n_acc_all))

        with span("spin.commit"):
            # update lengths / last tokens on pools
            out_np, n_acc_np = host
            win = np.asarray([winner_row[rid] for rid in ids], np.int64)
            n_acc = n_acc_np[win].astype(np.int64)
            out = out_np[win].astype(np.int64)
            out_len = n_acc + 1
            for i, (rid, row) in enumerate(zip(ids, win)):
                self.llm_pool.lengths[row] += out_len[i]
                self.llm_pool.last_token[row] = out[i, n_acc[i]]
                j = self.assignment[rid]
                srow = self.ssm_pools[j].row_of[rid]
                self.ssm_pools[j].lengths[srow] += out_len[i]
                self.ssm_pools[j].last_token[srow] = out[i, n_acc[i]]
            return n_acc, out, out_len

    def _verify_packed(self, cand, last, W: int, tree_rows=None):
        """Packed verification via request decomposition (§V-A) at the
        slot's max granted depth W.  Paged: the packed KV is the cohort's
        live blocks, gathered fragment-by-fragment from the pool — no flat
        packed copy, no padded grid.  ``tree_rows`` (tree mode) maps pool
        row -> (main row, node offset, branch depth): the query layout
        gains ancestor bitmasks, gathered slots gain node tags, and block
        owners remap to the main row so branches attend the shared
        prefix."""
        N = self.llm_pool.capacity
        if self.paged:
            bt, _ = self.llm_pool.block_table_array()
            ids_np, owner_np = self.llm_pool.live_blocks()
            lens_np = np.asarray(self.llm_pool.lengths, np.int64)
            inp = jnp.concatenate([last, cand], axis=1)   # (N, W+1)
            if tree_rows is not None:
                q_rows, q_pos, q_seg, q_anc = D.build_tree_row_layout(
                    lens_np, W, tree_rows)
                owner_np, block_node = self._tree_block_maps(
                    ids_np, owner_np, tree_rows, W)
                logits, cache = self.llm.verify_paged_tree(
                    self.llm_pool.cache, inp.reshape(1, -1),
                    jnp.asarray(q_pos.astype(np.int32)),
                    jnp.asarray(q_seg), jnp.asarray(q_rows), bt,
                    jnp.asarray(ids_np), jnp.asarray(owner_np),
                    jnp.asarray(q_anc), jnp.asarray(block_node),
                    self.fused_cfg)
            else:
                q_rows, q_pos, q_seg = D.build_query_layout(lens_np, W)
                logits, cache = self.llm.verify_paged(
                    self.llm_pool.cache, inp.reshape(1, -1),
                    jnp.asarray(q_pos.astype(np.int32)),
                    jnp.asarray(q_seg), jnp.asarray(q_rows), bt,
                    jnp.asarray(ids_np), jnp.asarray(owner_np),
                    self.fused_cfg)
            self.llm_pool.cache = cache
            return logits[0].reshape(N, W + 1, -1)
        lens_np = np.maximum(np.asarray(self.llm_pool.lengths), 1)
        plan = D.plan_decomposition(
            [int(n) for n in lens_np],
            align=min(128, _bucket(int(lens_np.max()), 16)))
        # bucket the packed size to bound retraces
        total_b = _bucket(plan.total, self.ecfg.packed_bucket)
        gb = np.zeros(total_b, np.int32)
        gs = np.zeros(total_b, np.int32)
        valid = np.zeros(total_b, bool)
        gb[:plan.total] = plan.gather_b
        gs[:plan.total] = plan.gather_s
        valid[:plan.total] = plan.valid
        self.last_plan = plan
        q_rows, q_pos, q_seg = D.build_query_layout(
            [int(n) for n in lens_np], W)
        override = D.make_attn_override(gb, gs, valid, q_rows)
        inp = jnp.concatenate([last, cand], axis=1)          # (N, W+1)
        tokens_flat = inp.reshape(1, -1)
        logits, cache = T.verify_step_packed(
            self.llm.params, self.llm.cfg, self.llm_pool.cache,
            tokens=tokens_flat, positions=jnp.asarray(q_pos),
            segments=jnp.asarray(q_seg), attn_override=override)
        self.llm_pool.cache = cache
        return logits[0].reshape(N, W + 1, -1)

    def _kv_cells_per_ssm(self, assign, ids, depths):
        """Attended KV cells per request, per SSM, for the timing model.

        Continuous batching makes per-slot batches ragged: requests on one
        SSM have genuinely different context lengths.  Padded verification
        attends the uniform max-length grid (a scalar, same for every
        SSM); packed verification attends each request's true context,
        normalised so the total matches the decomposition plan's packed
        cell count (alignment overhead included)."""
        if not ids:
            return 0.0
        gamma = max(depths[rid] for rid in ids)
        if self.paged:
            # attended cells are block-granular: a request costs its
            # allocated blocks (live context rounded up to whole blocks)
            raw = {rid: float(self.llm_pool.allocated_cells(rid))
                   for rid in ids}
            if not self.ecfg.use_packed_verify:
                # padded paged decode attends the bucketed widest table
                return float(max(raw.values()))
            cells = []
            for j in range(len(self.ssms)):
                vals = [raw[rid] for rid in ids if assign.get(rid) == j]
                cells.append(float(np.mean(vals)) if vals else 0.0)
            return cells
        if not (self.ecfg.use_packed_verify and hasattr(self, "last_plan")):
            return float(np.max(self.llm_pool.lengths)) + gamma + 1
        raw = {rid: float(self.llm_pool.lengths[self.llm_pool.row_of[rid]])
               + gamma + 1 for rid in ids}
        scale = self.last_plan.total / max(1.0, sum(raw.values()))
        cells = []
        for j in range(len(self.ssms)):
            vals = [raw[rid] * scale for rid in ids if assign.get(rid) == j]
            cells.append(float(np.mean(vals)) if vals else 0.0)
        return cells

    def _accept_rates_per_ssm(self, assign, ids, n_acc, depths):
        rates = []
        for j in range(len(self.ssms)):
            vals = [n_acc[i] / depths[rid] for i, rid in enumerate(ids)
                    if assign.get(rid) == j]
            rates.append(float(np.mean(vals)) if vals else 0.5)
        return rates

    def _simulate_slot(self, per_ssm_batch, mb, kv_cells_per_req=0.0,
                       prefill_time: float = 0.0,
                       depth_per_req=None,
                       verify_extra_per_req=None) -> P.SimResult:
        cost = self.cost
        if self.ecfg.straggler_mitigation:
            cost = self._with_straggler_mitigation(cost, per_ssm_batch)
        return P.simulate(cost, per_ssm_batch, mb, kv_cells_per_req,
                          prefill_time=prefill_time,
                          depth_per_req=depth_per_req,
                          verify_extra_per_req=verify_extra_per_req)

    def _with_straggler_mitigation(self, cost, per_ssm_batch):
        """Inject random stragglers; mitigation re-dispatches the straggling
        micro-batch to the fastest live SSM (bounded delay)."""
        jitter = np.random.default_rng(self.slots).exponential(
            1.0, len(self.ssms))
        slow = jitter > self.ecfg.straggler_factor
        if not slow.any():
            return cost
        per_tok = list(cost.ssm_time_per_token)
        fastest = float(min(t for j, t in enumerate(per_tok)
                            if j not in self.failed_ssms))
        for j in range(len(per_tok)):
            if slow[j] and per_ssm_batch[j] > 0:
                self.straggler_redispatches += 1
                # re-dispatch: pay the fastest replica's time + small penalty
                per_tok[j] = fastest * 1.5
        return dataclasses.replace(cost, ssm_time_per_token=per_tok)

    # ------------------------------------------------------------- runs --
    def run(self, max_slots: int = 1000) -> dict:
        for _ in range(max_slots):
            rec = self.step()
            if rec.get("done") and not self.scheduler.outstanding:
                break
        return self.stats()

    def stats(self) -> dict:
        lat = [r.latency for r in self.requests.values()
               if r.latency is not None]
        ttft = [r.first_token_time - r.arrival
                for r in self.requests.values()
                if r.first_token_time is not None]
        summ = slo_summary(self.requests.values())
        return {
            "slo_aware": self.slo_aware,
            "slo": {**summ.asdict(),
                    "goodput_under_slo":
                        summ.goodput_under_slo(self.sim_time)},
            "kv_layout": "paged" if self.paged else "dense",
            "kv_blocks": (self.llm_pool.num_blocks if self.paged else None),
            "prefill_chunk": (self.ecfg.prefill_chunk if self.chunked
                              else 0),
            "spec_shape": "tree" if self.tree else "linear",
            "fused_kernels": "on" if self.fused else "off",
            # tile config of every fused attention site (None = unfused)
            "fused_config": (dataclasses.asdict(self.fused_cfg)
                             if self.fused else None),
            "kv_dtype": self.kv_dtype,
            "spec_branches": self.branches,
            "verify_tokens": self.verify_tokens_total,
            "tree_forks": self.tree_forks,
            "tree_adoptions": self.tree_adoptions,
            "gamma": self.gamma_ctl.stats,
            "accepted_tokens": self.accepted_tokens,
            "prefill_tokens": self.prefill_tokens_total,
            "sim_time": self.sim_time,
            "goodput_sim": self.accepted_tokens / max(self.sim_time, 1e-9),
            "ttft_p50": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p95": float(np.percentile(ttft, 95)) if ttft else 0.0,
            "drafted": self.total_drafted,
            "switch": self.switcher.stats,
            "scheduler": self.scheduler.stats,
            "mean_latency": float(np.mean(lat)) if lat else 0.0,
            "p95_latency": float(np.percentile(lat, 95)) if lat else 0.0,
            "straggler_redispatches": self.straggler_redispatches,
            "mean_accept": float(np.mean([
                total / n for total, n in self._accept_by_req.values()]))
            if self._accept_by_req else 0.0,
        }
