#!/usr/bin/env python3
"""Sweep the arrival rate of an open-loop mix on one configuration, in
one process, to find the knee: the highest rate at which the backlog
does not grow over the window.

    python3 bench/sweep.py --config qwen2-7b-spin-0.5b --traffic chat-sweep \\
        --rates 0.2,0.3,0.4 --seconds 40 --seed 5

Each rate is served as a run serves its cell (warm-up, the mix's ramp,
a window of ``--seconds``) on a fresh engine; the models and their
compiled programs are shared.  Every rate prints one JSON line: the
queue of requests waiting for a row at the start and the end of the
window, the requests due and finished in it, and the end-to-end
readings.  Needs the chip, like ``run.py``."""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, spec  # noqa: E402

READ = ("ttft_p90_ms", "tpot_p50_ms", "itl_p90_ms", "output_tok_s",
        "step_ms_p50")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, args.config)
    base = spec.load_traffic(args.traffic)
    jax = device.setup_jax(spec.ROOT)
    dev = device.require_tpu(jax, 1)
    peak = spec.peaks_for(spec.load_peaks(), dev.device_kind)
    meter = device.CompileMeter(jax.monitoring)
    readers = {n: spec.load_reader(n) for n in READ}
    from harness import program
    program.import_program()
    from harness.session import Session
    bundles = None
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        sess = Session(cfg, dict(base, rate_per_s=rate), args.seed, peak,
                       meter, t0)
        sess.serve(args.seconds, bundles=bundles)
        bundles = (sess.llm, sess.ssms)
        row = {"config": args.config, "traffic": args.traffic,
               "rate_per_s": rate, "seed": args.seed,
               "waiting_at_start": sess.backlog[0],
               "waiting_at_end": sess.backlog[1],
               "due_in_window": sum(1 for r in sess.requests.values()
                                    if sess.in_window(r.due)),
               "finished_in_window": sum(
                   1 for r in sess.requests.values()
                   if r.finished is not None and sess.in_window(r.finished)),
               "running_at_end": len(sess.engine.scheduler.running),
               "compiles_in_window": sess.compiles_in_window,
               "setup_s": sess.setup_s}
        for name, r in readers.items():
            row[name] = r.read(sess)
        print(json.dumps(row), flush=True)
        sess.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
