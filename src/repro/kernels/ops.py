"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to :func:`interpret_mode`: False on TPU (compiled
Mosaic), True on the CPU (kernel body executed by the Pallas interpreter —
how this repo validates TPU kernels without TPU hardware).  Any other
backend is an error: there is no silent interpreted path on a device."""

from __future__ import annotations

import jax

from repro.kernels import autotune, quant
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_decode import fused_paged_decode as _fused_decode
from repro.kernels.fused_verify import fused_paged_verify as _fused_verify
from repro.kernels.paged_attention import (
    paged_decode_attention as _paged_decode,
    paged_verify_attention as _paged_verify)
from repro.kernels.verify_attention import verify_attention as _verify


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted on this backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and run interpreted on the "
        f"CPU; backend {backend!r} has neither path")


def verify_attention(q, k, v, q_seg, q_pos, kv_seg, kv_pos, *,
                     bq: int = 128, bk: int = 128, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _verify(q, k, v, q_seg, q_pos, kv_seg, kv_pos, bq=bq, bk=bk,
                   interpret=interpret)


def flash_attention(q, k, v, *, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _flash(q, k, v, window=window, bq=bq, bk=bk, interpret=interpret)


def decode_attention(q, k, v, lengths, *, bk=None, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _decode(q, k, v, lengths, bk=bk, interpret=interpret)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           k_scale=None, v_scale=None, *,
                           interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _paged_decode(q, k_pool, v_pool, block_tables, lengths,
                         k_scale, v_scale, interpret=interpret)


def paged_verify_attention(q, k_pool, v_pool, pool_seg, pool_pos,
                           q_seg, q_pos, block_ids, block_owner,
                           k_scale=None, v_scale=None, *,
                           bq: int = 128, interpret=None):
    if interpret is None:
        interpret = interpret_mode()
    return _paged_verify(q, k_pool, v_pool, pool_seg, pool_pos,
                         q_seg, q_pos, block_ids, block_owner,
                         k_scale=k_scale, v_scale=v_scale,
                         bq=bq, interpret=interpret)


# ------------------------------------------------- fused (autotuned) path --

def _resolve_config(kind, q, k_pool, gamma_max, shape, config):
    """Dispatch-time autotune-cache lookup: explicit config wins, else the
    cached winner for this (arch, gamma_max, block_size, shape) key, else
    the safe default (autotune.DEFAULT_CONFIG — never implicit tuning)."""
    if config is not None:
        return config
    return autotune.get_config(
        kind, H=q.shape[-2], Kh=k_pool.shape[2], D=q.shape[-1],
        gamma_max=gamma_max, block_size=k_pool.shape[1], shape=shape,
        kv_dtype=quant.dtype_name(k_pool.dtype))


def fused_paged_verify(q, k_pool, v_pool, pool_seg, pool_pos,
                       q_seg, q_pos, block_ids, block_owner,
                       q_anc=None, block_node=None,
                       k_scale=None, v_scale=None, *,
                       config=None, gamma_max: int = 0, interpret=None):
    """Single-launch packed verification (kernels/fused_verify.py): KV
    streams straight from the pool, no gathered copy.  ``config`` (a
    ``autotune.FusedConfig``) pins the tile shapes; None consults the
    autotune cache with the default fallback."""
    if interpret is None:
        interpret = interpret_mode()
    shape = "tree" if block_node is not None else "linear"
    cfg = _resolve_config("verify", q, k_pool, gamma_max, shape, config)
    return _fused_verify(q, k_pool, v_pool, pool_seg, pool_pos,
                         q_seg, q_pos, block_ids, block_owner,
                         q_anc, block_node, k_scale, v_scale,
                         bq=cfg.bq, bk=cfg.bk,
                         depth=cfg.depth, interpret=interpret)


def fused_paged_decode(q, k_pool, v_pool, pool_seg, pool_pos,
                       q_seg, q_pos, block_tables,
                       k_scale=None, v_scale=None, *,
                       config=None, gamma_max: int = 0, interpret=None):
    """Single-launch multi-token paged decode (kernels/fused_decode.py)
    with block-table prefetch double-buffered against tile compute."""
    if interpret is None:
        interpret = interpret_mode()
    cfg = _resolve_config("decode", q, k_pool, gamma_max, "linear", config)
    return _fused_decode(q, k_pool, v_pool, pool_seg, pool_pos,
                         q_seg, q_pos, block_tables, k_scale, v_scale,
                         bk=cfg.bk, depth=cfg.depth, interpret=interpret)
