"""Build the system under test through the program's own constructors:
its ``ModelConfig``, ``spec_decode.Bundle``, ``launch/serve.make_selector``,
``EngineConfig`` and ``SpinEngine``.  Each model is of the architecture
its configuration names (``bench/archs/<model_type>.py``, found by
``spec.load_arch``), whose weights are made on the device in one jitted
call per model from the seed.  Import this module only after JAX is set
up: it imports the program from ``src/`` beside ``bench/``."""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

from harness.spec import ROOT, load_arch
from harness.weights import model_key


def import_program(root: str = ROOT):
    """Put ``<root>/src`` on the path; fail where the program is absent."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


@dataclasses.dataclass(frozen=True)
class Model:
    """One model of a configuration: its architecture's module and the
    sizes that module read from the configuration."""
    arch: object
    sizes: object


def models(cfg: dict, root: str = ROOT):
    """(target, [drafters]) as ``Model``s from a configuration file.  A
    drafter without a ``model_type`` of its own is of the target's."""
    if "model_type" not in cfg:
        raise KeyError(f"{cfg['name']}: the configuration names no "
                       "model_type")

    def model(name, hf, model_type):
        arch = load_arch(model_type, root)
        return Model(arch, arch.sizes(name, hf))

    target = model(cfg["name"] + ".target", cfg, cfg["model_type"])
    drafters = [model(f"{cfg['name']}.drafter{i}", d,
                      d.get("model_type", cfg["model_type"]))
                for i, d in enumerate(cfg["drafters"])]
    for m in drafters:
        if m.sizes.vocab != target.sizes.vocab:
            raise ValueError(f"{m.sizes.name} has vocab {m.sizes.vocab}, the "
                             f"target {target.sizes.vocab}: drafts are "
                             "verified token for token")
    return target, drafters


@functools.lru_cache(maxsize=None)
def _maker(m: Model, padded_vocab: int):
    import jax
    return jax.jit(lambda key: m.arch.program_params(m.sizes, key,
                                                     padded_vocab))


def make_bundle(m: Model, seed: int, index: int, dtype: str = "bfloat16"):
    """A ``spec_decode.Bundle`` holding model ``index``'s weights, made
    from the seed on the device; checked against the tree the program's
    ``abstract_params`` describes."""
    import jax
    from repro.core import spec_decode as sd
    from repro.models import transformer as T
    mc = m.arch.model_config(m.sizes, dtype)
    params = _maker(m, mc.padded_vocab)(model_key(seed, index))
    if dtype != "bfloat16":
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        T.abstract_params(mc))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError(f"{m.sizes.name}: weight tree differs from the "
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
