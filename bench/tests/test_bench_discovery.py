"""Finding a cell's files by name, the shape of BENCHMARK.json, and the
refusal to run anywhere but on a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

from harness import device, program, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(^hidden_size$|intermediate|latent|state_size|projection|_dim$|"
                   r"_rank$|head_size|expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(REPO)


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cfg = spec.load_config(bench, w["config"], REPO)
        assert cfg["name"] == w["config"]
        mix = spec.load_traffic(w["traffic"], REPO)
        assert mix["rate_per_s"] > 0
        for trace in (0, 1):
            for m in spec.metrics_for(bench, w["name"], trace):
                assert callable(spec.load_reader(m["name"], REPO).read)


def test_per_layer_metrics_move_a_metric_of_their_cells(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.metrics_for(bench, w["name"], 0)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = spec.metrics_for(bench, w["name"], 1)
        assert per
        for m in per:
            assert m["moves"] in e2e


def test_benchmark_file_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        assert sorted(c["reduced"]) == sorted(
            spec.load_config(bench, c["name"], REPO)["reduced"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


def test_new_files_are_found_without_editing_any(tmp_path, bench):
    root = str(tmp_path)
    shutil.copytree(os.path.join(BENCH, "configs"),
                    os.path.join(root, "bench", "configs"))
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    os.path.join(root, "bench", "traffic"))
    before = {p: open(os.path.join(root, "bench", d, p)).read()
              for d in ("configs", "traffic")
              for p in os.listdir(os.path.join(root, "bench", d))}
    cfg = spec.load_config(bench, "qwen2-7b-spin-0.5b", REPO)
    cfg["name"] = "qwen2-1.5b-other"
    with open(os.path.join(root, "bench", "configs",
                           "qwen2-1.5b-other.json"), "w") as f:
        json.dump(cfg, f)
    mix = spec.load_traffic("chat-sweep", REPO)
    mix["rate_per_s"] = 9.0
    with open(os.path.join(root, "bench", "traffic", "chat-new.json"), "w") as f:
        json.dump(mix, f)
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "qwen2-1.5b.new", "config": "qwen2-1.5b-other",
         "traffic": "chat-new", "chips": 1, "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    b = spec.load_benchmark(root)
    w = spec.workload(b, "qwen2-1.5b.new")
    assert spec.load_config(b, w["config"], root)["name"] == "qwen2-1.5b-other"
    assert spec.load_traffic(w["traffic"], root)["rate_per_s"] == 9.0
    assert spec.metrics_for(b, "qwen2-1.5b.new", 1)
    for p, text in before.items():
        d = "configs" if p in os.listdir(os.path.join(BENCH, "configs")) \
            else "traffic"
        assert open(os.path.join(root, "bench", d, p)).read() == text


def test_unknown_names_are_errors(bench):
    with pytest.raises(KeyError):
        spec.workload(bench, "no-such-cell")
    with pytest.raises(KeyError):
        spec.peaks_for(spec.load_peaks(REPO), "TPU v99")
    assert spec.peaks_for(spec.load_peaks(REPO),
                          "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_refuses_a_cpu():
    import jax
    with pytest.raises(SystemExit) as e:
        device.require_tpu(jax, 1)
    assert e.value.code != 0


def test_run_exits_without_a_result_off_the_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen2-7b.chat", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_no_program_no_run(tmp_path):
    with pytest.raises(FileNotFoundError):
        program.import_program(str(tmp_path))
