"""Reduce a profiler trace of the window to the per-layer numbers.

A TPU trace holds host planes (the harness's own ``bench.*`` spans,
written with ``TraceAnnotation``, and the runtime's events that launch
programs) and a device plane, whose ``XLA Modules`` line has one event
per program run (named ``<jit name>(<fingerprint>)``, with its
``run_id``) and whose ``XLA Ops`` line has the operations inside them.
The program jits lambdas, so its models' programs are all named
``jit__lambda``: the name cannot tell verify from drafting.  So
``instrument`` wraps the models' entry points in
``bench.program.<kind>`` host spans, and a program run goes to the kind
whose span was open on the host when it was launched.  The launch is
found from the run's ``run_id``: the host event that enqueued it
(``DoEnqueueProgram``) either runs inside the launching call or on a
worker thread, inside an event that a flow links back to the event in
the launching call.

The reduction, in the trace's own time base:

* the traced window: from the first to the last ``bench.*`` span;
* device busy time: the union of the operations' intervals in it;
* device time per kind: the durations of the program runs of that kind;
* idle time: the gaps between busy intervals, each piece given to the
  innermost harness span (``bench.step``, ``bench.add``, ...) open over
  it on the host.

``events(planes)`` flattens a trace into plain records, so the tests run
the reduction on a small recorded trace kept as JSON."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List

KINDS = {"verify_paged": "verify", "decode_paged": "draft",
         "prefill": "prefill", "append_paged": "prefill"}
TOP = 10
ENQUEUE = "DoEnqueueProgram"


@dataclasses.dataclass
class Event:
    device: bool
    line: str
    name: str
    start: float      # ns
    dur: float        # ns
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    kind_s: Dict[str, float]
    steps: int
    breakdown: dict


def instrument(sess):
    """Wrap the models' jitted entry points in ``bench.program.<kind>``
    host spans (trace runs only)."""
    import jax
    for b in [sess.engine.llm, *sess.engine.ssms]:
        for attr, kind in KINDS.items():
            fn = getattr(b, attr)

            def wrapped(*a, _fn=fn, _label="bench.program." + kind, **k):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(*a, **k)
            setattr(b, attr, wrapped)


def uninstrument(models):
    for b in models:
        for attr in KINDS:
            b.__dict__.pop(attr, None)


def events(planes) -> List[Event]:
    """Flatten ``ProfileData`` planes into events: every host event, and
    the device's program runs and operations."""
    out = []
    for plane in planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for e in line.events:
                out.append(Event(dev, line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns), dict(e.stats)))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _innermost(spans: List[Event], starts: List[float], t: float):
    """The span with the latest start among those open at ``t``
    (``spans`` sorted by start, ``starts`` their starts)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end >= t:
            return spans[i]
        i -= 1
    return None


def _launches(host: List[Event]) -> Dict[object, float]:
    """run_id -> host time of the call that launched that program run."""
    producers = {e.stats["_p"]: e for e in host if "_p" in e.stats}
    by_line = defaultdict(list)
    for e in host:
        by_line[e.line].append(e)
    out = {}
    for line, evs in by_line.items():
        evs.sort(key=lambda e: (e.start, -e.dur))
        open_stack: List[Event] = []
        for e in evs:
            while open_stack and open_stack[-1].end < e.start:
                open_stack.pop()
            if e.name == ENQUEUE and "run_id" in e.stats:
                t = e.start
                for parent in reversed(open_stack):
                    if "_c" in parent.stats and parent.stats["_c"] in producers:
                        t = producers[parent.stats["_c"]].start
                        break
                out[e.stats["run_id"]] = t
            open_stack.append(e)
    return out


def _short(name: str) -> str:
    return name.split(" = ")[0].split("(")[0]


def reduce_events(evs: List[Event]) -> Reduced:
    host = [e for e in evs if not e.device]
    spans = [e for e in host if e.name.startswith("bench.")]
    if not spans:
        return Reduced(0.0, 0.0, {}, 0, {"device_ops": [], "idle_gaps": []})
    w0 = min(e.start for e in spans)
    w1 = max(e.end for e in spans)
    progs = sorted((e for e in spans if e.name.startswith("bench.program.")),
                   key=lambda e: e.start)
    prog_starts = [e.start for e in progs]
    launch = _launches(host)
    modules = sorted((e for e in evs if e.device and e.line == "XLA Modules"),
                     key=lambda e: e.start)
    ops = [e for e in evs if e.device and e.line == "XLA Ops"
           and e.end > w0 and e.start < w1]
    kind_ns = defaultdict(float)
    mod_kind = []
    for m in modules:
        kind = "other"
        t = launch.get(m.stats.get("run_id"))
        if t is not None:
            p = _innermost(progs, prog_starts, t)
            if p is not None:
                kind = p.name[len("bench.program."):]
        mod_kind.append(kind)
        d = min(m.end, w1) - max(m.start, w0)
        if d > 0:
            kind_ns[kind] += d
    busy = _union((max(e.start, w0), min(e.end, w1)) for e in ops)
    busy_ns = sum(e - s for s, e in busy)
    mod_starts = [m.start for m in modules]
    op_ns = defaultdict(float)
    for e in ops:
        i = bisect.bisect_right(mod_starts, e.start) - 1
        key = "no program"
        if i >= 0 and modules[i].end >= e.start:
            kind = mod_kind[i]
            key = (f"{kind}:{_short(e.name)}" if kind != "other"
                   else _short(modules[i].name))
        op_ns[key] += min(e.end, w1) - max(e.start, w0)
    gaps = []
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((s, e))
    outer = sorted((e for e in spans if not e.name.startswith("bench.program.")),
                   key=lambda e: e.start)
    outer_starts = [e.start for e in outer]
    cuts = sorted({x for h in outer for x in (h.start, h.end)})
    idle = defaultdict(float)
    for s, e in gaps:
        # split the gap at every harness span boundary inside it; each
        # piece goes to the innermost span open over it
        lo, hi = bisect.bisect_right(cuts, s), bisect.bisect_left(cuts, e)
        points = [s] + cuts[lo:hi] + [e]
        for a, b in zip(points, points[1:]):
            h = _innermost(outer, outer_starts, (a + b) / 2)
            idle[h.name if h is not None else "no harness span"] += b - a
    steps = sum(1 for e in spans if e.name == "bench.step")
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
        kind_s={k: v * 1e-9 for k, v in kind_ns.items()}, steps=steps,
        breakdown={"device_ops": [[k, v * 1e-9] for k, v in top_ops],
                   "idle_gaps": [[k, v * 1e-9] for k, v in top_idle]})


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return reduce_events(events(ProfileData.from_file(paths[-1]).planes))
