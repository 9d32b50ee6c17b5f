"""Finding a cell's files and its models' architectures by name, the
shape of BENCHMARK.json, and the refusal to run anywhere but on a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
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


def _toy_checkout(tmp_path, drafter_type="qwen2"):
    """A checkout that adds the toy architecture and a configuration
    naming it, with a Qwen2 drafter (``drafter_type``; None: a toy
    drafter that names no type of its own), and edits no other file."""
    root = str(tmp_path)
    archs = os.path.join(root, "bench", "archs")
    os.makedirs(archs)
    os.makedirs(os.path.join(root, "bench", "configs"))
    shutil.copy(os.path.join(BENCH, "archs", "qwen2.py"), archs)
    shutil.copy(os.path.join(TESTS, "data", "toy_arch.py"),
                os.path.join(archs, "toy.py"))
    with open(os.path.join(TESTS, "data", "tiny.json")) as f:
        tiny = json.load(f)
    cfg = dict(tiny, name="toy-moe", model_type="toy", num_hidden_layers=4,
               num_experts=4, num_experts_per_tok=2)
    cfg["drafters"] = [dict(d, model_type=drafter_type) if drafter_type
                       else dict(d, num_hidden_layers=2, num_experts=4,
                                 num_experts_per_tok=2)
                       for d in tiny["drafters"]]
    with open(os.path.join(root, "bench", "configs", "toy-moe.json"),
              "w") as f:
        json.dump(cfg, f)
    bench = spec.load_benchmark(REPO)
    bench["configs"] = bench["configs"] + [
        {"name": "toy-moe", "source": "test", "file":
         "bench/configs/toy-moe.json", "reduced": [], "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    b = spec.load_benchmark(root)
    return root, spec.load_config(b, "toy-moe", root)


def test_a_new_architecture_is_added_with_files_only(tmp_path):
    """A toy whose stack holds two layer shapes (a dense layer, then a
    layer of routed experts, a period at a time) is built, weighted,
    referenced and counted through the harness as it is."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import flops, reference, weights
    from harness.session import Call
    program.import_program()
    from repro.models import transformer as T
    root, cfg = _toy_checkout(tmp_path)
    target, (drafter,) = program.models(cfg, root)
    assert target.arch.__file__ == os.path.join(root, "bench", "archs",
                                                "toy.py")
    assert drafter.arch.__file__.endswith(os.path.join("archs", "qwen2.py"))
    assert target.sizes.vocab == drafter.sizes.vocab == 512
    # weighted: the tree is the program's, checked against its own
    b = program.make_bundle(target, 2**33 + 5, 0, dtype="float32")
    assert set(b.params["scan"]) == {"u0_attn", "u1_moe"}
    assert b.params["scan"]["u1_moe"]["w_gate"].shape == (2, 4, 64, 128)
    assert b.params["scan"]["u0_attn"]["w_gate"].shape == (2, 64, 128)
    # referenced: the reference walks the mixed stack as the program does
    toks = np.random.default_rng(1).integers(0, 512, (1, 256))
    with jax.default_matmul_precision("highest"):
        logits, _ = T.apply(b.params, b.cfg, tokens=jnp.asarray(toks))
    pick = np.asarray(jnp.argmax(logits[..., :512], -1)).astype(np.int32)
    nxt = np.full((1, 256), -1, np.int32)
    nxt[:, 5:250] = pick[:, 5:250]
    key = weights.model_key(2**33 + 5, 0)
    gaps, control = reference.compiled_gaps(target, True)(
        key, toks.astype(np.int32), nxt)
    assert float(jnp.max(gaps)) < 1e-4
    assert float(jnp.max(control)) > 0
    # counted
    call = Call("step", 0.0, 1.0, rows=3, width=4, ctx_cells=300)
    f, byte = target.arch.verify_work(target.sizes, call)
    assert f > 0 and byte > target.arch.weight_bytes(target.sizes)
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    assert flops.verify_least_s(target, peak, call) == \
        max(f / 1e12, byte / 1e11)
    assert target.arch.prefill_flops(target.sizes, 10) > \
        target.arch.token_flops(target.sizes, 9)
    # nothing but the new architecture, its configuration and the
    # benchmark's entry was written
    written = sorted(os.path.relpath(os.path.join(d, f), root)
                     for d, _, fs in os.walk(root) for f in fs
                     if "__pycache__" not in d)
    assert written == ["BENCHMARK.json", "bench/archs/qwen2.py",
                       "bench/archs/toy.py", "bench/configs/toy-moe.json"]


def test_a_drafter_without_a_model_type_is_of_the_targets(tmp_path):
    with open(os.path.join(BENCH, "tests", "data", "tiny.json")) as f:
        tiny = json.load(f)
    assert "model_type" not in tiny["drafters"][0]
    target, (drafter,) = program.models(tiny)
    assert drafter.arch is target.arch is spec.load_arch("qwen2")
    root, cfg = _toy_checkout(tmp_path, drafter_type=None)
    target, (drafter,) = program.models(cfg, root)
    assert drafter.arch is target.arch
    assert type(drafter.sizes).__name__ == "Toy"


@pytest.mark.parametrize("where", ["target", "drafter"])
def test_an_unknown_model_type_names_the_file_it_looked_for(tmp_path,
                                                           where):
    root, cfg = _toy_checkout(tmp_path)
    if where == "target":
        cfg["model_type"] = "qwen9"
    else:
        cfg["drafters"][0]["model_type"] = "qwen9"
    with pytest.raises(KeyError, match="bench/archs/qwen9.py"):
        program.models(cfg, root)
    with pytest.raises(KeyError, match="model_type"):
        program.models({k: v for k, v in cfg.items()
                        if k != "model_type"}, root)


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
