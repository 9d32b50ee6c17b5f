"""Find the pieces of a cell by the names in ``BENCHMARK.json``.

Every configuration, traffic mix and metric reader lives in a file of
its own under ``bench/``; the harness never lists them in code, so a
later change adds a cell by adding files and entries only."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(bench: dict, name: str, root: str = ROOT) -> str:
    """The configuration's file: its ``file`` in BENCHMARK.json, else
    ``bench/configs/<name>.json``."""
    for c in bench.get("configs", []):
        if c["name"] == name:
            return os.path.join(root, c["file"])
    return os.path.join(root, "bench", "configs", f"{name}.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    return _json(config_path(bench, name, root))


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def load_peaks(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "peaks.json"))


def peaks_for(peaks: dict, device_kind: str) -> dict:
    """The row of ``bench/peaks.json`` for this device kind.  A device
    that is not in the table is an error, never a default."""
    for row in peaks["devices"]:
        if row["device_kind"] == device_kind:
            return row
    raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
    An entry without ``workloads`` applies to every cell that reports
    the end-to-end metric it moves (per-layer) or to every cell
    (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def load_reader(name: str, root: str = ROOT):
    """The reader module ``bench/metrics/<name>.py``; its ``read(run)``
    returns the metric's value, or None where it found nothing to read."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
