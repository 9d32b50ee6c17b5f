"""Compiles for a described (not attached) TPU v5e at published widths.

The TPU compiler is installed with JAX, so a chip can be described and
compiled for on a host without one.  These tests compile the fused Pallas
kernels of the served path at LLaMA-7B and LLaMA-68M attention widths,
and the target's whole fused verify and decode steps, and check that each
program holds a compiled kernel (``tpu_custom_call``) and fits one chip.
They catch what interpret mode cannot: block shapes off the (8, 128)
tile, vector ops the TPU compiler does not lower, programs that do not
fit the device.  Nothing runs, so nothing here says anything about
results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import spin_llama
from repro.kernels import autotune, ops
from repro.kernels.fused_decode import fused_paged_decode
from repro.kernels.fused_verify import fused_paged_verify
from repro.models import transformer as T
from repro.serving.paged import decode_step_paged, verify_step_paged

WIDTHS = {c.name: c for c in (spin_llama.LLAMA_7B, spin_llama.LLAMA_68M)}
KV_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}
HBM_BYTES = 16 * 2**30  # one v5e chip
ROWS, GAMMA, NUM_BLOCKS, TABLE = 4, 4, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: a program compiled for a described chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler or library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [
        None if s is None else jax.ShapeDtypeStruct(*s, sharding=sharding)
        for s in specs
    ]


@pytest.mark.parametrize("kv", list(KV_DTYPES))
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("arch", list(WIDTHS))
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_fused_kernel_compiles(one_chip, kind, arch, bs, kv):
    cfg = WIDTHS[arch]
    H, Kh, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    i32, kvdt = jnp.int32, KV_DTYPES[kv]
    pool = ((NUM_BLOCKS, bs, Kh, D), kvdt)
    slots = ((NUM_BLOCKS, bs), i32)
    scale = ((NUM_BLOCKS, bs, Kh), jnp.float32) if kv != "bf16" else None
    if kind == "decode":
        q = ((ROWS, GAMMA + 1, H, D), jnp.bfloat16)
        qs, bt = ((ROWS, GAMMA + 1), i32), ((ROWS, TABLE), i32)
        args = _shapes(one_chip, q, pool, pool, slots, slots, qs, qs, bt)
        lowered = fused_paged_decode.lower(
            *args, *_shapes(one_chip, scale, scale), interpret=False
        )
    else:
        Tq = ROWS * (GAMMA + 1)
        q, qs, ids = ((Tq, H, D), jnp.bfloat16), ((Tq,), i32), ((NUM_BLOCKS,), i32)
        args = _shapes(one_chip, q, pool, pool, slots, slots, qs, qs, ids, ids)
        lowered = fused_paged_verify.lower(
            *args, None, None, *_shapes(one_chip, scale, scale), interpret=False
        )
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_fused_target_step_fits_one_chip(one_chip, kind, monkeypatch):
    """The 16-layer LLaMA-7B target's fused paged step, as the engine
    jits it, compiles with the kernel inside and fits one chip's HBM."""
    # on this CPU-only host the kernels would otherwise pick interpret mode
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(spin_llama.LLAMA_7B, n_layers=16)

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = place(T.abstract_params(cfg))
    cache = place(jax.eval_shape(lambda: T.init_paged_cache(cfg, NUM_BLOCKS, 16)))
    fused = autotune.DEFAULT_CONFIG
    i32 = jnp.int32
    if kind == "decode":
        toks, lens, bt = _shapes(
            one_chip, ((ROWS, GAMMA + 1), i32), ((ROWS,), i32), ((ROWS, TABLE), i32)
        )
        step = jax.jit(
            lambda p, c, t, n, b: decode_step_paged(
                p, cfg, c, tokens=t, lengths=n, block_tables=b, fused_cfg=fused
            )
        )
        lowered = step.lower(params, cache, toks, lens, bt)
    else:
        Tq = ROWS * (GAMMA + 1)
        row, col, bt, ids = _shapes(
            one_chip,
            ((1, Tq), i32),
            ((Tq,), i32),
            ((ROWS, TABLE), i32),
            ((NUM_BLOCKS,), i32),
        )
        step = jax.jit(
            lambda p, c, t, pos, seg, qr, b, i, o: verify_step_paged(
                p,
                cfg,
                c,
                tokens=t,
                positions=pos,
                segments=seg,
                q_rows=qr,
                block_tables=b,
                block_ids=i,
                block_owner=o,
                fused_cfg=fused,
            )
        )
        lowered = step.lower(params, cache, row, row, row, col, bt, ids, ids)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert used < HBM_BYTES, used
