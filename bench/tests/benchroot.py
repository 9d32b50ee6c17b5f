"""Build a checkout for the CPU tests: ``BENCHMARK.json`` with the tiny
cells, the real metric readers, architectures and program, and a peaks
row for the CPU (the tests skip the look for a chip, so nothing they
print is a device number)."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def make_root(tmp) -> str:
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    for d in ("metrics", "archs"):
        os.symlink(os.path.join(BENCH, d), os.path.join(root, "bench", d))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    shutil.copy(os.path.join(HERE, "data", "tiny.json"),
                os.path.join(root, "bench", "configs", "tiny.json"))
    with open(os.path.join(HERE, "data", "tiny-chat.json")) as f:
        mix = json.load(f)
    with open(os.path.join(root, "bench", "traffic", "tiny-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"].append({"device_kind": "cpu", "bf16_flops": 1e12,
                             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})
    with open(os.path.join(root, "bench", "peaks.json"), "w") as f:
        json.dump(peaks, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1,
                           "why": "CPU tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
