"""Build the system under test through the program's own constructors:
its ``ModelConfig``, ``spec_decode.Bundle``, ``launch/serve.make_selector``,
``EngineConfig`` and ``SpinEngine``.  The weights are the benchmark's
(``harness.weights``), made on the device in one jitted call per model
from the seed.  Import this module only after JAX is set up: it imports
the program from ``src/`` beside ``bench/``."""

from __future__ import annotations

import functools
import os
import sys

from harness.spec import ROOT
from harness.weights import Qwen2, model_key, program_params


def import_program(root: str = ROOT):
    """Put ``<root>/src`` on the path; fail where the program is absent."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def models(cfg: dict):
    """(target, [drafters]) as ``Qwen2`` sizes from a configuration file."""
    target = Qwen2.from_hf(cfg["name"] + ".target", cfg)
    drafters = [Qwen2.from_hf(f"{cfg['name']}.drafter{i}", d)
                for i, d in enumerate(cfg["drafters"])]
    for m in drafters:
        if m.vocab != target.vocab:
            raise ValueError(f"{m.name} has vocab {m.vocab}, the target "
                             f"{target.vocab}: drafts are verified token "
                             "for token")
    return target, drafters


def model_config(m: Qwen2, dtype: str = "bfloat16"):
    from repro.models.config import ATTN, ModelConfig
    return ModelConfig(name=m.name, family="dense", n_layers=m.layers,
                       d_model=m.hidden, n_heads=m.heads,
                       n_kv_heads=m.kv_heads, d_ff=m.inter,
                       vocab_size=m.vocab, head_dim=m.head_dim,
                       qkv_bias=True, tie_embeddings=m.tied, unit=(ATTN,),
                       rope_theta=m.theta, norm_eps=m.eps, dtype=dtype)


@functools.lru_cache(maxsize=None)
def _maker(m: Qwen2, padded_vocab: int):
    import jax
    return jax.jit(lambda key: program_params(m, key, padded_vocab))


def make_bundle(m: Qwen2, seed: int, index: int, dtype: str = "bfloat16"):
    """A ``spec_decode.Bundle`` holding model ``index``'s weights, made
    from the seed on the device; checked against the tree the program's
    ``abstract_params`` describes."""
    import jax
    from repro.core import spec_decode as sd
    from repro.models import transformer as T
    mc = model_config(m, dtype)
    params = _maker(m, mc.padded_vocab)(model_key(seed, index))
    if dtype != "bfloat16":
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        T.abstract_params(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError(f"{m.name}: weight tree differs from the "
                         f"program's: {got} != {want}")
    return sd.Bundle(mc, params)


def engine_config(cfg: dict, seed: int, **override):
    """The program's ``EngineConfig`` from the configuration's ``engine``
    settings (launcher defaults elsewhere)."""
    from repro.serving.engine import EngineConfig
    e = dict(cfg["engine"])
    e.update(override)
    return EngineConfig(
        gamma=e["gamma"], max_len=e["max_len"], capacity=e["capacity"],
        block_size=e["block_size"], prefill_chunk=e["prefill_chunk"],
        token_budget=e["token_budget"], kv_layout="paged",
        fused_kernels=e["fused_kernels"], kv_dtype=e["kv_dtype"],
        slo_aware=False, seed=seed & 0x7FFFFFFF)


def build_engine(cfg: dict, llm, ssms, seed: int, group_of: dict, **override):
    from repro.launch import serve
    from repro.serving.engine import SpinEngine
    ecfg = engine_config(cfg, seed, **override)
    sel = serve.make_selector(cfg["engine"]["selector"], len(ssms),
                              ecfg.capacity, seed=seed & 0x7FFFFFFF,
                              group_of=group_of)
    return SpinEngine(llm, ssms, sel, ecfg)
