#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload qwen2-7b.chat --seed 7 --seconds 51 --trace 0

Run it from the root of a checkout that holds ``BENCHMARK.json``,
``bench/`` and the program under ``src/``.  The cell names its
configuration (``bench/configs/``), whose models name their
architecture (``bench/archs/``), and its traffic mix (``bench/traffic/``);
the metrics it reports are the cell's end-to-end metrics with
``--trace 0`` and its per-layer metrics with ``--trace 1``, each read by
``bench/metrics/<name>.py``.

A run: checks that JAX's first device is a TPU (there is no CPU
fallback), makes every model's weights on the device from ``--seed``,
builds the program's engine, warms every shape the traffic reaches,
starts the traffic at steady state, measures ``--seconds`` on the host's
clock, reads the device's peak memory, frees the program and checks every
request served in the window against the plain float32 reference.  The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.  JAX's persistent compilation
cache lives in ``JAX_COMPILATION_CACHE_DIR`` if that is set, else in
``.jax_cache/`` at the root of the checkout."""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from harness import device, spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts):
    print("run:", *parts, file=sys.stderr, flush=True)


def run_cell(root, name, seed, seconds, trace, require_chip=True,
             engine_patch=None):
    """One run of cell ``name`` from the checkout at ``root``: returns
    the result object (``checks`` last).  ``require_chip=False`` skips
    the look for a TPU (the CPU tests drive the rest of a run with it);
    ``engine_patch`` is called on the engine before traffic starts."""
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    cfg = spec.load_config(bench, cell["config"], root)
    mix = spec.load_traffic(cell["traffic"], root)
    wanted = spec.metrics_for(bench, cell["name"], bool(trace))
    readers = {m["name"]: spec.load_reader(m["name"], root) for m in wanted}

    jax = device.setup_jax(root)
    dev = (device.require_tpu(jax, cell["chips"]) if require_chip
           else jax.devices()[0])
    peak = spec.peaks_for(spec.load_peaks(root), dev.device_kind)
    meter = device.CompileMeter(jax.monitoring)
    from harness import program
    program.import_program(root)
    from harness.session import Session

    sess = Session(cfg, mix, seed, peak, meter, PROCESS_START, root=root)
    trace_dir = (os.path.join(root, "bench", ".traces",
                              f"{cell['name']}.{seed}") if trace else None)
    sess.serve(seconds, trace_dir, engine_patch=engine_patch, log=say)
    sample, gaps, _ = sess.verify()

    values = {}
    for m in wanted:
        v = readers[m["name"]].read(sess)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    _report_samples(sess)
    limit = float(cfg["check"]["max_logit_gap"])
    least = int(cfg["check"]["requests_finished"])
    widest = float(max(gaps)) if len(gaps) else float("inf")
    served = int(sum(len(r.req.emitted) for r in sample))
    finished = sum(1 for r in sample if r.finished is not None)
    correct = widest <= limit and finished >= least
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "requests_finished": {"value": finished, "limit": least},
    }
    result = {
        "correct": correct,
        "attempted": sum(1 for r in sess.requests.values()
                         if sess.in_window(r.due)),
        "failed": sum(1 for g in gaps if g > limit),
        "metrics": values,
        "device": device.describe(jax, dev, sess),
    }
    if sess.trace is not None:
        result["breakdown"] = sess.trace.breakdown
    result["checks"] = checks
    say(f"checked {len(sample)} requests, {served} served tokens, against "
        "the float32 reference")
    print(f"check max_logit_gap: {widest} (limit: at most {limit})",
          file=sys.stderr, flush=True)
    print(f"check requests_finished: {finished} (limit: at least {least})",
          file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run_cell(spec.ROOT, args.workload, args.seed, args.seconds,
                      args.trace)
    print(json.dumps(result), flush=True)
    return 0


def _report_samples(sess):
    """Sample counts behind each tail, and how late the generator ran."""
    n_first = sum(1 for r in sess.requests.values()
                  if r.first_token is not None and sess.in_window(r.first_token))
    n_done = sum(1 for r in sess.requests.values()
                 if r.finished is not None and sess.in_window(r.finished))
    say(f"window {sess.window_s:.3f} s after {sess.setup_s:.3f} s of set-up: "
        f"{n_first} first tokens, {n_done} requests finished, "
        f"{sum(1 for c in sess.calls if c.kind == 'step' and sess.in_window(c.start))}"
        f" steps, {sess.compiles_in_window} compiles in the window, "
        f"generator late by up to {sess.max_lateness * 1e3:.3f} ms")


if __name__ == "__main__":
    sys.exit(main())
