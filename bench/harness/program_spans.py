"""Device idle per phase of the engine's step, from the program's own
``spin.*`` host spans.

The program opens ``spin.*`` spans (``TraceAnnotation``) inside
``SpinEngine.step``: ``spin.step`` around the step and one span per
phase inside it (``docs/SERVING.md`` lists them).  A ``--trace 1`` run
writes them into the same ``.xplane.pb`` as the device's operations, on
one clock.  This module reads that trace once per process and reduces
it over the window and step count ``tracing.reduce_events`` uses (first
to last ``bench.*`` span; the ``bench.step`` spans):

* every idle gap of the device is split at each ``spin.*`` and
  ``bench.*`` span boundary inside it, and each piece goes to the
  innermost ``spin.*`` span open over it on the host (and is marked
  with the innermost ``bench.*`` span, to tell idle inside
  ``bench.step`` from the rest);
* ``spin.step`` carries the KV counters ``kv_used`` and ``kv_alloc``.

A trace with no device operations or no ``spin.step`` (a CPU run, or a
program without the spans) reduces to None, and so do the readers."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from harness.tracing import Event, _innermost, _union, events

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = {
    "schedule": ("spin.schedule", "spin.admit", "spin.prefill"),
    "draft": ("spin.assign", "spin.draft", "spin.precompute"),
    "verify": ("spin.verify", "spin.rollback"),
    "commit": ("spin.catchup", "spin.commit"),
}


@dataclasses.dataclass
class Phases:
    steps: int
    # device idle seconds per (innermost spin.* span, innermost bench.*
    # span) over it; "" where no such span is open
    idle: Dict[Tuple[str, str], float]
    kv_used_share: Optional[float]

    def idle_s(self, spin=None, bench=None) -> float:
        """Idle seconds under the ``spin`` span names (all if None) and
        inside ``bench`` (anywhere if None)."""
        return sum(v for (s, b), v in self.idle.items()
                   if (spin is None or s in spin)
                   and (bench is None or b == bench))

    def idle_ms(self, phase: str) -> float:
        """Device idle per traced step, in ms, whose innermost ``spin.*``
        span is one of ``phase``'s."""
        return self.idle_s(PHASES[phase]) * 1e3 / self.steps


def reduce_events(evs: List[Event]) -> Optional[Phases]:
    host = [e for e in evs if not e.device]
    bench = sorted((e for e in host if e.name.startswith("bench.")
                    and not e.name.startswith("bench.program.")),
                   key=lambda e: e.start)
    spin = sorted((e for e in host if e.name.startswith("spin.")),
                  key=lambda e: e.start)
    steps = sum(1 for e in bench if e.name == "bench.step")
    if not steps or not any(e.name == "spin.step" for e in spin):
        return None
    w0 = min(e.start for e in bench)
    w1 = max(e.end for e in bench)
    busy = _union((max(e.start, w0), min(e.end, w1)) for e in evs
                  if e.device and e.line == "XLA Ops"
                  and e.end > w0 and e.start < w1)
    if not busy:
        return None
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    spin_starts = [e.start for e in spin]
    bench_starts = [e.start for e in bench]
    cuts = sorted({x for h in spin + bench for x in (h.start, h.end)})
    idle = defaultdict(float)
    for s, e in gaps:
        lo, hi = bisect.bisect_right(cuts, s), bisect.bisect_left(cuts, e)
        points = [s] + cuts[lo:hi] + [e]
        for a, b in zip(points, points[1:]):
            mid = (a + b) / 2
            sp = _innermost(spin, spin_starts, mid)
            bp = _innermost(bench, bench_starts, mid)
            idle[(sp.name if sp else "", bp.name if bp else "")] += b - a
    shares = [100.0 * e.stats["kv_used"] / e.stats["kv_alloc"]
              for e in spin if e.name == "spin.step"
              and e.stats.get("kv_alloc") and w0 <= e.start < w1]
    return Phases(steps=steps, idle={k: v * 1e-9 for k, v in idle.items()},
                  kv_used_share=sum(shares) / len(shares) if shares else None)


def trace_path(seed: int) -> Optional[str]:
    """The newest trace under ``bench/.traces/*.<seed>/``: the one the
    run with that seed wrote."""
    paths = glob.glob(os.path.join(BENCH_DIR, ".traces", f"*.{seed}", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _parse(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(path).planes)


_CACHE: Dict[str, Optional[Phases]] = {}


def phases(run) -> Optional[Phases]:
    """The reduction of the trace run ``run`` wrote, parsed once."""
    path = trace_path(run.seed)
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = reduce_events(_parse(path))
    return _CACHE[path]


def idle_ms(run, phase: str) -> Optional[float]:
    p = phases(run)
    return None if p is None else p.idle_ms(phase)
