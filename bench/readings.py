#!/usr/bin/env python3
"""Read the number that decides ``correct`` over many seeds, in one
process, for setting a cell's limit.

    python3 bench/readings.py --workload qwen2-7b.chat --seeds 1,2,3 --seconds 25
    python3 bench/readings.py --workload qwen2-7b.chat --seeds 1,2,3 --seconds 25 --kv-dtype int8

Each seed is served as a run of the cell serves it (its residents, ramp
and a window of ``--seconds``), then every request served in the window
is checked against the float32 reference.  Every seed prints one JSON line: the program's
widest logit gap (the lower reading) and the widest gap of the tokens
the reference computed with float8 matrices would have served (the
float8 control's reading).  ``--kv-dtype int8`` or ``fp8`` runs the
program with its own quantized KV cache: its widest gap is then that
control's reading.  Needs the chip, like ``run.py``."""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--kv-dtype", default=None, choices=["int8", "fp8"])
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    jax = device.setup_jax(spec.ROOT)
    dev = device.require_tpu(jax, cell["chips"])
    peak = spec.peaks_for(spec.load_peaks(), dev.device_kind)
    meter = device.CompileMeter(jax.monitoring)
    from harness import program
    program.import_program()
    from harness.session import Session
    override = {"kv_dtype": args.kv_dtype} if args.kv_dtype else {}
    bundles = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        sess = Session(cfg, mix, seed, peak, meter, t0, override)
        sess.serve(args.seconds, bundles=bundles)
        bundles = (sess.llm, sess.ssms)
        sample, mine, control = sess.verify(control=not args.kv_dtype)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "kv_dtype": args.kv_dtype or cfg["engine"]["kv_dtype"],
            "requests": len(sample),
            "finished": sum(1 for r in sample if r.finished is not None),
            "served_tokens": int(sum(len(r.req.emitted) for r in sample)),
            "max_logit_gap": float(max(mine)) if len(mine) else None,
            "per_request": [float(x) for x in mine],
            "float8_control_gap": (float(max(control)) if len(control)
                                   else None),
            "compiles_in_window": sess.compiles_in_window,
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
