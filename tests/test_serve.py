"""The serving launcher's construction helpers: the compile-cache
location, a zoo built from given configs, and ``build_server`` serving a
zoo it is handed (how ``chip_smoke.py`` drives published widths)."""

import os

import pytest

from repro.configs import spin_llama
from repro.launch import serve
from repro.models.config import reduced

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of making them, so no
    test turns the persistent cache on for the rest of its process."""
    calls = []
    monkeypatch.setattr(serve.jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_compile_cache_env_dir_is_used_as_is(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    assert serve.use_compile_cache() == "/some/cache"
    assert config_updates == []


def test_compile_cache_defaults_to_one_ignored_checkout_dir(
    monkeypatch, config_updates
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", path)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _tiny(cfg, vocab=128, layers=1):
    return reduced(
        cfg,
        d_model=32,
        n_heads=2,
        n_kv_heads=2,
        head_dim=16,
        vocab_size=vocab,
        n_layers=layers,
    )


def test_build_zoo_builds_the_given_configs():
    llm_cfg = _tiny(spin_llama.LLAMA_7B, layers=2)
    ssm_cfgs = [_tiny(spin_llama.LLAMA_68M), _tiny(spin_llama.LLAMA_265M)]
    llm, ssms = serve.build_zoo(128, 3, llm_cfg=llm_cfg, ssm_cfgs=ssm_cfgs)
    assert llm.cfg is llm_cfg
    assert [b.cfg for b in ssms] == ssm_cfgs
    assert llm.params["scan"]["u0_attn"]["wq"].shape == (2, 32, 2, 16)
    with pytest.raises(ValueError, match="vocab"):
        serve.build_zoo(256, llm_cfg=llm_cfg, ssm_cfgs=ssm_cfgs)


def test_build_server_serves_a_given_zoo(monkeypatch):
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    zoo = serve.build_zoo(
        128,
        llm_cfg=_tiny(spin_llama.LLAMA_7B, layers=2),
        ssm_cfgs=[_tiny(spin_llama.LLAMA_68M)],
    )
    argv = ["--requests", "3", "--capacity", "2", "--vocab", "128"]
    server, reqs, args = serve.build_server(argv, zoo=zoo)
    assert server.llm is zoo[0] and server.ssms == zoo[1]
    assert len(reqs) == 3 and args.capacity == 2
    with pytest.raises(SystemExit):
        serve.build_server(["--vocab", "256"], zoo=zoo)
