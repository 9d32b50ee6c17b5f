"""One run of a cell: build the system under test, warm every shape its
traffic reaches, start the traffic at steady state, measure a window on
the host's clock, then check what the window served against the plain
reference.  ``run.py`` is the command, ``readings.py`` and ``sweep.py``
drive the same sequence (``Session.serve`` and ``Session.verify``); the
metric readers under ``bench/metrics/`` read the attributes of a
``Session`` after its window."""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from harness import program, reference, traffic, warmup
from harness.spec import ROOT
from harness.weights import model_key

TRACE_S = 4.0       # seconds of the window a --trace 1 run records
clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """What the harness saw of one request, on the host's clock."""
    rid: int
    due: float
    prompt_len: int
    output_len: int
    req: object
    first_token: Optional[float] = None
    finished: Optional[float] = None
    deliveries: List[tuple] = dataclasses.field(default_factory=list)
    seen: int = 0


@dataclasses.dataclass
class Call:
    """One call into the engine: ``step`` or ``add`` (``add_requests``),
    with the record the call returned (``SpinEngine.step``'s dict)."""
    kind: str
    start: float
    end: float
    rows: int = 0
    width: int = 0
    ctx_cells: int = 0
    tokens: int = 0
    record: dict = dataclasses.field(default_factory=dict)


class Session:
    def __init__(self, cfg: dict, mix: dict, seed: int, peak: dict,
                 meter, process_start: float, override: Optional[dict] = None,
                 root: str = ROOT):
        self.cfg = cfg
        self.mix = mix
        self.seed = int(seed)
        self.peak = peak
        self.meter = meter
        self.process_start = process_start
        self.override = override or {}
        self.target, self.drafters = program.models(cfg, root)
        self.schedule = traffic.Schedule(mix, seed, self.target.sizes.vocab)
        self.requests: Dict[int, Record] = {}
        self.live: Dict[int, Record] = {}
        self.calls: List[Call] = []
        self.next_rid = 0
        self.traffic_start = 0.0
        self.window_start = self.window_end = 0.0
        self.trace = None
        self.trace_span = (0.0, 0.0)
        self.compiles_in_window = 0
        self.window_counters = None
        self.memory_peak_bytes = 0
        self.max_lateness = 0.0

    # ------------------------------------------------------------ set-up --
    @property
    def setup_s(self) -> float:
        return self.window_start - self.process_start

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def in_window(self, t: float) -> bool:
        return self.window_start <= t < self.window_end

    def serve(self, seconds: float, trace_dir: Optional[str] = None,
              bundles=None, engine_patch=None, log=None):
        """Build, warm, start the traffic at steady state and measure a
        window of ``seconds``: what every driver of a session runs.
        ``engine_patch`` is called on the engine before traffic starts;
        ``log`` gets a line after the build and after the warm-up."""
        import jax
        self.build(bundles)
        if log:
            mem = jax.devices()[0].memory_stats() or {}
            log(f"models and pools built: {mem.get('bytes_in_use', 0)} "
                f"bytes in use of {mem.get('bytes_limit', 0)}")
        self.warm()
        if log:
            log(f"warmed: {self.meter.count} programs compiled or loaded "
                f"({self.meter.cache_hits} from the cache) in "
                f"{clock() - self.process_start:.3f} s")
        if engine_patch is not None:
            engine_patch(self.engine)
        self.start_traffic()
        self.measure(seconds, trace_dir)
        if trace_dir is not None:
            from harness import tracing
            self.trace = tracing.reduce_dir(trace_dir)

    def verify(self, control: bool = False):
        """Free the program and check what the window served: returns
        the requests compared, the program's widest gap for each, and
        with ``control`` the float8 control's (see ``check``)."""
        sample = self.sample()
        self.free()
        mine, theirs = self.check(sample, control)
        return sample, mine, theirs

    def build(self, bundles=None):
        """Make the models' weights from the seed and the engine.  Given
        the ``(llm, ssms)`` of an earlier session of the same
        configuration, its models take the new weights and keep their
        jitted entry points (a process that reads many seeds)."""
        import jax
        if bundles is None:
            self.llm = program.make_bundle(self.target, self.seed, 0)
            self.ssms = [program.make_bundle(m, self.seed, i + 1)
                         for i, m in enumerate(self.drafters)]
        else:
            self.llm, self.ssms = bundles
            for i, (b, m) in enumerate(zip([self.llm, *self.ssms],
                                           [self.target, *self.drafters])):
                b.params = program.make_bundle(m, self.seed, i).params
        jax.block_until_ready([b.params for b in [self.llm, *self.ssms]])
        self.engine = self.new_engine()

    def new_engine(self):
        group_of = _Labels(self.schedule)
        return program.build_engine(self.cfg, self.llm, self.ssms, self.seed,
                                    group_of, **self.override)

    def warm(self):
        """Compile or load every program the window can reach; the
        engine that served the warm-up is drained and serves the window."""
        warmup.warm(self)

    # ----------------------------------------------------------- traffic --
    def _request(self, due: float):
        from repro.data.workloads import Request
        i = self.next_rid
        self.next_rid += 1
        p, o = self.schedule.lengths(i)
        req = Request(rid=i, dataset=self.schedule.label(i), difficulty=0.0,
                      prompt=self.schedule.tokens(i), max_new=o)
        rec = Record(rid=i, due=due, prompt_len=p, output_len=o, req=req)
        self.requests[i] = rec
        self.live[i] = rec
        return req

    def _next_due(self) -> float:
        return self.traffic_start + self.schedule.due(
            self.next_rid - self.schedule.resident)

    def _due_requests(self, now: float):
        out = []
        while self._next_due() <= now:
            due = self._next_due()
            if self.window_start and due >= self.window_start:
                self.max_lateness = max(self.max_lateness, now - due)
            out.append(self._request(due))
        return out

    def start_traffic(self):
        """Put the mix's residents in flight (admitted and prefilled, as
        at steady state), then start the arrivals' clock."""
        n = self.schedule.resident
        if n > self.engine.ecfg.capacity:
            raise ValueError(f"{n} residents do not fit "
                             f"{self.engine.ecfg.capacity} rows")
        if n:
            res = [self._request(clock()) for _ in range(n)]
            self._call("add", self.engine.add_requests, res)
            for _ in range(n + 1):
                if all(self.requests[r.rid].first_token is not None
                       for r in res):
                    break
                self._call("step", self.engine.step)
        self.traffic_start = clock()

    def _call(self, kind: str, fn, *args):
        eng = self.engine
        rows = ctx = 0
        if kind == "step":
            pool = eng.llm_pool
            ctx = int(sum(pool.lengths[r] for r in pool.row_of.values()))
        t0 = clock()
        with TraceAnnotation("bench." + kind):
            rec = fn(*args)
        t1 = clock()
        rec = rec or {}
        rows = int(rec.get("active", 0))
        self.calls.append(Call(kind, t0, t1, rows=rows,
                               width=eng.gamma_max, ctx_cells=ctx,
                               tokens=int(rec.get("tokens", 0)),
                               record=rec))
        with TraceAnnotation("bench.observe"):
            self._observe(t1)
        return rec

    def _observe(self, t: float):
        for rid in list(self.live):
            rec = self.live[rid]
            n = len(rec.req.emitted or ())
            if n > rec.seen:
                rec.deliveries.append((t, n - rec.seen))
                rec.seen = n
                if rec.first_token is None:
                    rec.first_token = t
            if rec.req.done:
                rec.finished = t
                del self.live[rid]

    def drive(self, until: float):
        """Serve the traffic until the host clock reaches ``until``."""
        while True:
            now = clock()
            if now >= until:
                return
            new = self._due_requests(now)
            if new:
                self._call("add", self.engine.add_requests, new)
            if self.live:
                self._call("step", self.engine.step)
            else:
                wait = min(until, self._next_due()) - clock()
                if wait > 0:
                    with TraceAnnotation("bench.wait_arrival"):
                        time.sleep(wait)

    # ------------------------------------------------------------ window --
    def measure(self, seconds: float, trace_dir: Optional[str] = None):
        import jax
        self.drive(self.traffic_start + self.mix["ramp_s"])
        waiting0 = len(self.engine.scheduler.waiting)
        c0, d0 = self.meter.count, self.engine.total_drafted
        n_calls = len(self.calls)
        self.window_start = clock()
        end = self.window_start + seconds
        if trace_dir is None:
            self.drive(end)
        else:
            # the trace records the window's last TRACE_S seconds and is
            # written out after the window has closed
            self.drive(max(self.window_start, end - TRACE_S))
            from harness import tracing
            tracing.instrument(self)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t0 = clock()
            self.drive(end)
            jax.block_until_ready(self.engine.llm_pool.cache)
            self.trace_span = (t0, clock())
        self.window_end = clock()
        jax.block_until_ready(self.engine.llm_pool.cache)
        if trace_dir is not None:
            jax.profiler.stop_trace()
            tracing.uninstrument([self.engine.llm, *self.engine.ssms])
        self.compiles_in_window = self.meter.count - c0
        self.backlog = (waiting0, len(self.engine.scheduler.waiting))
        steps = [c for c in self.calls[n_calls:] if c.kind == "step"]
        self.window_counters = {
            "drafted": self.engine.total_drafted - d0,
            "committed": sum(c.tokens for c in steps),
            "rows_verified": sum(c.rows for c in steps)}
        self.memory_peak_bytes = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0)

    def traced_steps(self):
        t0, t1 = self.trace_span
        return [c for c in self.calls
                if c.kind == "step" and c.rows and t0 <= c.start
                and c.end <= t1]

    # ------------------------------------------------------------- check --
    def sample(self):
        """The requests the check compares: every one served a token in
        the window, finished or still in flight at its close."""
        return sorted((r for r in self.requests.values()
                       if any(self.in_window(t) for t, _ in r.deliveries)),
                      key=lambda r: r.rid)

    def free(self):
        """Drop the program's state and the models' weights so the
        reference has the chip; the models keep their jitted entry
        points for a later session's ``build(bundles)``."""
        self.engine = None
        for b in [self.llm, *self.ssms]:
            b.params = None
        gc.collect()

    def check(self, sample, control: bool = False):
        """Teacher-force the sample's prompts and served tokens through
        the reference.  Returns per request the widest gap between the
        reference's best logit and its logit for a served token (and,
        with ``control``, the widest gap of the float8 forward's picks)."""
        import jax.numpy as jnp
        fn = reference.compiled_gaps(self.target, control)
        key = model_key(self.seed, 0)
        mine, theirs = [], []
        for r in sample:
            s = np.concatenate([r.req.prompt, np.asarray(r.req.emitted)])
            S = reference.bucket(len(s))
            toks = np.zeros((1, S), np.int32)
            nxt = np.full((1, S), -1, np.int32)
            toks[0, :len(s)] = s
            nxt[0, r.prompt_len - 1:len(s) - 1] = s[r.prompt_len:]
            out = fn(key, jnp.asarray(toks), jnp.asarray(nxt))
            mine.append(float(jnp.max(out[0])))
            if control:
                theirs.append(float(jnp.max(out[1])))
        return mine, theirs


class _Labels(dict):
    """Request id -> class label for LBSS's grouping, drawn from the
    schedule on first use (LBSS reads it with ``get``)."""

    def __init__(self, schedule):
        super().__init__()
        self.schedule = schedule

    def get(self, rid, default=None):
        if isinstance(rid, int) and 0 <= rid < warmup.WARM_RID:
            return self.schedule.label(rid)
        return default

    def __bool__(self):
        return True
