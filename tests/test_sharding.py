"""Sharding-rule assignment + lowering machinery on a 1x1 mesh (the
512-device production meshes are exercised by launch/dryrun.py, which must
own its process — here we verify the same code paths cheaply)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.distributed import sharding as shd
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.optim import AdamW


class FakeMesh:
    """Minimal mesh stand-in exposing .shape for assign_spec tests."""
    def __init__(self, shape):
        self.shape = shape


def test_assign_spec_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = shd.serve_rules(False)
    # kv_heads=8 can't shard over model=16 -> cache_seq takes the model axis
    spec = shd.assign_spec(rules, ("cache_batch", "cache_seq", "kv_heads",
                                   "head_dim"), (128, 32768, 8, 128), mesh)
    assert spec == P("data", "model", None, None)
    # kv_heads=16 divides -> it gets the axis, seq stays unsharded
    spec = shd.assign_spec(rules, ("cache_batch", "cache_seq", "kv_heads",
                                   "head_dim"), (128, 32768, 16, 128), mesh)
    assert spec == P("data", None, "model", None)


def test_assign_spec_no_axis_reuse():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = shd.train_rules(False)
    # both vocab and heads want "model": only one (higher priority) gets it
    spec = shd.assign_spec(rules, ("vocab", "heads"), (32768, 48), mesh)
    assert tuple(spec).count("model") == 1


def test_assign_spec_multipod_batch():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = shd.train_rules(True)
    spec = shd.assign_spec(rules, ("batch", "seq"), (256, 4096), mesh)
    assert spec[0] == ("pod", "data")
    # batch=1 can't shard at all
    spec = shd.assign_spec(rules, ("batch", "seq"), (1, 4096), mesh)
    assert spec == P(None, None)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b",
                                  "zamba2-1.2b", "xlstm-350m"])
def test_full_param_sharding_tree_covers_every_leaf(arch):
    """Production-mesh shardings must exist for every parameter leaf and
    respect divisibility (checked via assign_spec internals)."""
    cfg = registry.get(arch)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = shd.train_rules(False)
    ab = T.abstract_params(cfg)
    ax = T.logical_axes(cfg)
    flat_ab = jax.tree.leaves(ab)
    def is_axes(a):
        return isinstance(a, tuple) and all(
            isinstance(e, (str, type(None))) for e in a)
    flat_ax = jax.tree.leaves(ax, is_leaf=is_axes)
    assert len(flat_ab) == len(flat_ax)
    for leaf, axes in zip(flat_ab, flat_ax):
        spec = shd.assign_spec(rules, axes, leaf.shape, mesh)
        for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if part is None:
                continue
            size = np.prod([mesh.shape[a] for a in
                            ((part,) if isinstance(part, str) else part)])
            assert dim % size == 0, (arch, axes, leaf.shape, spec)


def test_lowering_on_tiny_mesh_end_to_end():
    """Lower + compile a reduced train step on the real 1-device mesh with
    rule-driven shardings + constrain() active — same code path as dryrun."""
    cfg = registry.reduced_for("qwen2-0.5b")
    mesh = make_local_mesh()
    rules = shd.train_rules(False)
    opt = AdamW(lr=1e-3)
    ab = T.abstract_params(cfg)
    ax = T.logical_axes(cfg)
    sh = shd.sharding_tree(mesh, rules, ax, ab)
    ab_opt = opt.abstract_state(ab)
    step = T.make_train_step(cfg, opt, T.Opts(remat="dots"))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    jitted = jax.jit(step, in_shardings=(sh, None, None))
    with mesh, shd.use_rules(mesh, rules):
        lowered = jitted.lower(ab, ab_opt, batch)
    compiled = lowered.compile()
    assert compiled.cost_analysis()["flops"] > 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0


def test_collective_parser():
    from repro.launch.dryrun import collective_wire_bytes
    hlo = """
  %ar = f32[16,512]{1,0} all-reduce(f32[16,512]{1,0} %x), replica_groups={}
  %ag.1 = bf16[4,128]{1,0} all-gather(bf16[2,128]{1,0} %y), dimensions={0}
  %cp = f32[8]{0} collective-permute(f32[8]{0} %z)
"""
    out = collective_wire_bytes(hlo)
    assert out["all-reduce"] == 2 * 16 * 512 * 4
    assert out["all-gather"] == 4 * 128 * 2
    assert out["collective-permute"] == 8 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
