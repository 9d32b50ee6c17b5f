"""Find the pieces of a cell by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, metric reader and model architecture
lives in a file of its own under ``bench/``; the harness never lists
them in code, so a later change adds a cell, or a model of a new
architecture, by adding files and entries only."""

from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import os
import re
import sys

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
    return _module("bench_metric_" + name.replace(".", "_").replace("-", "_"),
                   path)


def load_arch(model_type: str, root: str = ROOT):
    """The architecture module ``bench/archs/<model_type>.py``, loaded
    once per file; a configuration, and each of its drafters, names it
    by its ``model_type``.  The module gives, with ``m`` the sizes it
    builds:

    - ``sizes(name, hf)``: hashable sizes from the configuration's (HF)
      dict, with ``name``, ``vocab`` and ``layers``;
    - ``model_config(m, dtype)``: the program's ``ModelConfig``;
    - ``program_params(m, model_key, padded_vocab)``: the program's
      weight tree, drawn with ``harness.weights`` from the model's key;
    - ``reference_forward(m, model_key, tokens, positions, control)``:
      the float32 stack, for ``harness.reference`` (see there);
    - ``matmul_params``, ``weight_bytes``, ``kv_bytes_per_token``,
      ``token_flops(m, context)``, ``prefill_flops(m, n)`` and
      ``verify_work(m, call)``: (FLOPs, bytes) of the verify pass of a
      traced step's ``session.Call``, whose ``record`` holds what the
      program's ``step`` returned.

    An unknown type is a KeyError naming the file looked for."""
    rel = f"bench/archs/{model_type}.py"
    path = os.path.join(root, *rel.split("/"))
    if not _ARCH.match(model_type) or not os.path.isfile(path):
        raise KeyError(f"no architecture {model_type!r}: {rel} not found "
                       f"under {root}")
    return _load_once(os.path.realpath(path))


_ARCH = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_-]*$")


@functools.lru_cache(maxsize=None)
def _load_once(path: str):
    # a name of its own file: the module is registered under it, so
    # that its dataclasses find their module while they are made
    stem = os.path.basename(path)[:-3].replace("-", "_")
    digest = hashlib.sha1(path.encode()).hexdigest()[:8]
    return _module(f"bench_arch_{stem}_{digest}", path)


def _module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
