"""Paged-KV attention plumbing for the serving engine (XLA path).

The paged ``CachePool`` stores each model's KV in a block pool
``(num_blocks, block_size, Kh, D)``; requests own ordered lists of physical
blocks (block tables).  The model forward never sees a dense
``(rows, max_len)`` grid: the override closures below route every attention
layer through the block table —

* **write**: new K/V is scattered straight into the owning request's tail
  block(s) (``flat = table[row, pos // bs] * bs + pos % bs``); rows without
  an allocated block (idle pool rows, padding) map to an out-of-range index
  and the scatter drops them;
* **read**: only *live* blocks are gathered — ``(B, nb_max * bs)`` for
  decode (nb_max = live blocks of the longest row, bucketed) and
  ``(M * bs,)`` for packed verification (M = live blocks of the verified
  cohort) — so per-step HBM traffic tracks the live context, not the pool
  capacity and not ``max_len``.

These mirror the Pallas kernels in ``kernels/paged_attention.py`` (the TPU
hot path, validated against the same oracles); like the rest of the model
stack, the engine's functional path uses the XLA formulation so results are
identical on any backend.

Entry points ``decode_step_paged`` / ``verify_step_paged`` wrap
``models.transformer`` with the right override; ``core.spec_decode.Bundle``
jits them per model (block tables are *traced* arguments, so a step never
retraces when the tables' contents change).

Invariants this plumbing relies on (owned by ``serving/pool.py``,
previously stated only in PR descriptions):

* **Block ownership** — ``row_of`` is a bijection from live request ids
  to pool rows, and each physical block belongs to at most one row's
  table; ``free_blocks + Σ allocated == num_blocks`` after any
  admit/evict/grow sequence (property-tested).  Rows not in ``row_of``
  own no blocks, which is what makes static-shape writes safe: their
  positions resolve out of range and the scatter drops them.
* **Attendability** — a KV slot is readable only when its block is in a
  live table AND its ``seg >= 0``; freshly allocated blocks are
  seg-invalidated so a previous owner's data can never be attended.
* **Speculation margin** — before decode/verify writes land, each
  participating row's table covers ``ctx + k_i + 1`` cells (granted
  depth + bonus token; draft pools add one more for the catch-up hole),
  and rollback scrubs ``[ctx + 1 + n_acc, ctx + W + 1)`` so rejected
  drafts are never attendable afterwards.
* **Budget unit** — the pool holds ``kv_budget // block_size`` physical
  blocks (plus the one-full-row deadlock-freedom floor); the scheduler
  accounts demand in block-rounded cells, so "budget exceeded" and
  "allocation fails" are the same event, not two models of it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import quant
from repro.models import config as C
from repro.models import transformer as T
from repro.models.layers import attention


def pool_dims(cache) -> Tuple[int, int]:
    """(num_blocks, block_size) of a paged cache tree."""
    for name, entry in cache.get("scan", {}).items():
        if isinstance(entry, dict) and "k" in entry:
            return entry["k"].shape[1], entry["k"].shape[2]
    for name, entry in cache.items():
        if isinstance(entry, dict) and "k" in entry:
            return entry["k"].shape[0], entry["k"].shape[1]
    raise ValueError("cache tree has no attention entries")


def _flat_write_idx(block_tables, positions, bs: int, oob: int):
    """Flat pool slot per (row, position); ``oob`` for unmapped positions
    (idle row / position beyond the row's allocated blocks) — the scatter
    drops those updates."""
    nb = block_tables.shape[1]
    lb = positions // bs
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(lb, 0, nb - 1), axis=1)
    ok = (positions >= 0) & (lb < nb) & (phys >= 0)
    return jnp.where(ok, phys * bs + positions % bs, oob)


def _write_kv(kv_cache, widx_flat, k_new, v_new, positions, segments,
              num_blocks: int, bs: int):
    """Scatter new K/V (+pos/seg) into the flattened pool; returns the
    updated (num_blocks, bs, ...) tree.  O(new tokens), not O(pool).

    Quantized pools (``k_scale``/``v_scale`` sidecar leaves present —
    kernels/quant.py) quantize each new token's K/V per (slot, head) on
    the way in and scatter the scales into the same flat slots, so the
    write stays one pass and no dequantized pool copy ever exists."""
    with jax.named_scope("kv_write"):
        Kh, hd = kv_cache["k"].shape[-2:]
        quantized = "k_scale" in kv_cache
        out = dict(kv_cache)
        for leaf, new in (("k", k_new), ("v", v_new)):
            src = new.reshape(-1, Kh, hd)
            pool = kv_cache[leaf]
            if quantized:
                src, scales = quant.quantize(src, pool.dtype)
                sp = kv_cache[leaf + "_scale"]
                out[leaf + "_scale"] = sp.reshape(num_blocks * bs, Kh) \
                    .at[widx_flat].set(scales) \
                    .reshape(num_blocks, bs, Kh)
            out[leaf] = pool.reshape(num_blocks * bs, Kh, hd) \
                .at[widx_flat].set(src.astype(pool.dtype)) \
                .reshape(num_blocks, bs, Kh, hd)
        out["pos"] = kv_cache["pos"].reshape(-1).at[widx_flat].set(
            positions.reshape(-1)).reshape(num_blocks, bs)
        out["seg"] = kv_cache["seg"].reshape(-1).at[widx_flat].set(
            segments.reshape(-1)).reshape(num_blocks, bs)
    return out


def _gather_dequant(new_cache, leaf, slot, num_blocks: int, bs: int, shape,
                    dtype):
    """Gather pool slots ``slot`` of ``leaf`` ('k'/'v') and, on a
    quantized pool, dequantize post-gather (the XLA fallback path — the
    Pallas kernels dequantize in-kernel instead)."""
    with jax.named_scope("kv_gather"):
        flat = new_cache[leaf].reshape(num_blocks * bs, *shape)
        g = flat[slot]
        if leaf + "_scale" not in new_cache:
            return g
        sc = new_cache[leaf + "_scale"].reshape(num_blocks * bs,
                                                shape[0])[slot]
        return quant.dequantize(g, sc, dtype)


def make_paged_decode_override(block_tables, num_blocks: int, bs: int):
    """Attention override for decode/draft/verify-padded over a paged pool.

    block_tables: (B, nb_max) int32, -1 = unallocated.  Queries of row b
    attend the gathered view of row b's blocks (write-then-read, so the new
    tokens attend each other causally like the dense path).
    """
    bt = block_tables.astype(jnp.int32)

    def override(q, k_new, v_new, positions, segments, kv_cache, cfg, opts):
        B, Tn = positions.shape
        widx = _flat_write_idx(bt, positions, bs, num_blocks * bs)
        new_cache = _write_kv(kv_cache, widx.reshape(-1), k_new, v_new,
                              positions, segments, num_blocks, bs)
        # gather each row's live blocks into a (B, nb_max*bs) view;
        # quantized pools dequantize the gathered slots (XLA fallback)
        nb_max = bt.shape[1]
        slot = (jnp.maximum(bt, 0) * bs)[:, :, None] + jnp.arange(bs)
        slot = slot.reshape(B, nb_max * bs)
        kg = _gather_dequant(new_cache, "k", slot, num_blocks, bs,
                             k_new.shape[2:], k_new.dtype)
        vg = _gather_dequant(new_cache, "v", slot, num_blocks, bs,
                             v_new.shape[2:], v_new.dtype)
        posg = new_cache["pos"].reshape(-1)[slot]
        segg = new_cache["seg"].reshape(-1)[slot]
        live = jnp.repeat(bt >= 0, bs, axis=1)
        segg = jnp.where(live, segg, -1)
        with jax.named_scope("paged_attention"):
            o = attention(q, kg, vg, q_positions=positions,
                          kv_positions=posg, q_segments=segments,
                          kv_segments=segg, window=cfg.sliding_window,
                          q_block=opts.q_block)
        return o, new_cache

    return override


def make_fused_decode_override(block_tables, num_blocks: int, bs: int,
                               fused_cfg):
    """Fused-kernel variant of :func:`make_paged_decode_override`: the
    write scatter is unchanged (O(new tokens)), but the read side is ONE
    ``kernels/fused_decode.fused_paged_decode`` launch streaming the
    row's blocks straight from the pool — the ``(B, nb_max * bs)``
    gathered view is never materialized.  ``fused_cfg`` is the
    ``kernels/autotune.FusedConfig`` pinning the tile shapes (resolved by
    the engine at construction; static under jit)."""
    from repro.kernels import ops
    bt = block_tables.astype(jnp.int32)

    def override(q, k_new, v_new, positions, segments, kv_cache, cfg, opts):
        widx = _flat_write_idx(bt, positions, bs, num_blocks * bs)
        new_cache = _write_kv(kv_cache, widx.reshape(-1), k_new, v_new,
                              positions, segments, num_blocks, bs)
        with jax.named_scope("paged_attention"):
            o = ops.fused_paged_decode(
                q, new_cache["k"], new_cache["v"], new_cache["seg"],
                new_cache["pos"], segments, positions, bt,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), config=fused_cfg)
        return o.astype(q.dtype), new_cache

    return override


def make_paged_verify_override(q_rows, block_tables, block_ids, block_owner,
                               num_blocks: int, bs: int,
                               q_anc=None, block_node=None):
    """Attention override for SPIN packed verification over a paged pool.

    q_rows: (Tq,) pool row per flattened query token; block_ids /
    block_owner: (M,) live physical blocks of the verified cohort and the
    row owning each (-1 owner = padding entry).  The packed KV is gathered
    fragment-by-fragment — no flat packed copy, no padded grid.

    Optional tree-speculation topology: ``q_anc`` (Tq,) is the per-query
    ancestor bitmask and ``block_node`` (M, bs) tags each gathered slot
    with its tree-node id (-1 committed, -2 dead, n >= 0 tree node); both
    omitted reduces to the linear Eq. 13 mask exactly.
    """
    q_rows = jnp.asarray(q_rows, jnp.int32)
    bt = block_tables.astype(jnp.int32)
    ids = jnp.maximum(jnp.asarray(block_ids, jnp.int32), 0)
    owner = jnp.asarray(block_owner, jnp.int32)
    M = ids.shape[0]
    anc = None if q_anc is None else \
        jnp.asarray(q_anc, jnp.int32).reshape(1, -1)
    node = None if block_node is None else \
        jnp.asarray(block_node, jnp.int32).reshape(1, M * bs)

    def override(q, k_new, v_new, positions, segments, kv_cache, cfg, opts):
        # q/k_new/v_new: (1, Tq, ·, hd); positions/segments: (1, Tq) with
        # segments = owning row (Eq. 13 segment ids)
        pos = positions[0]
        nb = bt.shape[1]
        lb = pos // bs
        phys = bt[q_rows, jnp.clip(lb, 0, nb - 1)]        # (Tq,)
        ok = (pos >= 0) & (lb < nb) & (phys >= 0)
        widx = jnp.where(ok, phys * bs + pos % bs, num_blocks * bs)
        # pool slots store seg=0 (valid), mirroring the dense cache
        new_cache = _write_kv(kv_cache, widx.reshape(-1), k_new, v_new,
                              positions, jnp.zeros_like(segments),
                              num_blocks, bs)
        slot = ((ids * bs)[:, None] + jnp.arange(bs)).reshape(M * bs)
        kg = _gather_dequant(new_cache, "k", slot, num_blocks, bs,
                             k_new.shape[2:], k_new.dtype)[None]
        vg = _gather_dequant(new_cache, "v", slot, num_blocks, bs,
                             v_new.shape[2:], v_new.dtype)[None]
        posg = new_cache["pos"].reshape(-1)[slot][None]
        slot_seg = new_cache["seg"].reshape(-1)[slot]
        segg = jnp.where((slot_seg >= 0) & (jnp.repeat(owner, bs) >= 0),
                         jnp.repeat(owner, bs), -1)[None]
        with jax.named_scope("paged_attention"):
            o = attention(q, kg, vg, q_positions=positions,
                          kv_positions=posg, q_segments=segments,
                          kv_segments=segg, window=cfg.sliding_window,
                          q_block=opts.q_block, q_anc=anc, kv_node=node)
        return o, new_cache

    return override


def make_fused_verify_override(q_rows, block_tables, block_ids, block_owner,
                               num_blocks: int, bs: int,
                               q_anc=None, block_node=None, fused_cfg=None):
    """Fused-kernel variant of :func:`make_paged_verify_override`: one
    ``kernels/fused_verify.fused_paged_verify`` launch replaces the
    ``(M * bs,)`` fragment gather + packed attention pair, for linear and
    tree shapes alike (``q_anc``/``block_node`` thread straight into the
    kernel's inline mask)."""
    from repro.kernels import ops
    q_rows = jnp.asarray(q_rows, jnp.int32)
    bt = block_tables.astype(jnp.int32)
    ids = jnp.asarray(block_ids, jnp.int32)
    owner = jnp.asarray(block_owner, jnp.int32)
    anc = None if q_anc is None else jnp.asarray(q_anc, jnp.int32)
    node = None if block_node is None else jnp.asarray(block_node, jnp.int32)

    def override(q, k_new, v_new, positions, segments, kv_cache, cfg, opts):
        pos = positions[0]
        nb = bt.shape[1]
        lb = pos // bs
        phys = bt[q_rows, jnp.clip(lb, 0, nb - 1)]        # (Tq,)
        ok = (pos >= 0) & (lb < nb) & (phys >= 0)
        widx = jnp.where(ok, phys * bs + pos % bs, num_blocks * bs)
        new_cache = _write_kv(kv_cache, widx.reshape(-1), k_new, v_new,
                              positions, jnp.zeros_like(segments),
                              num_blocks, bs)
        with jax.named_scope("paged_attention"):
            o = ops.fused_paged_verify(
                q[0], new_cache["k"], new_cache["v"], new_cache["seg"],
                new_cache["pos"], segments[0], pos, ids, owner, anc, node,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"), config=fused_cfg)
        return o[None].astype(q.dtype), new_cache

    return override


# ------------------------------------------------------- model entrypoints --

def decode_step_paged(params, cfg, cache, *, tokens, lengths, block_tables,
                      segments=None, fused_cfg=None,
                      opts: T.Opts = T.Opts()):
    """Paged analogue of ``transformer.decode_step``: T new tokens per row,
    K/V written to / read from the rows' block tables.

    ``segments`` (optional, (B, T)) marks padding query tokens with -1:
    their KV writes land seg-invalidated (never attendable) and their
    outputs are masked garbage the caller ignores.  This is how **chunked
    prefill** appends a prompt chunk into an existing block table — a
    (1, chunk) call whose queries attend the row's prior context blocks
    plus themselves causally.  It is the same query-segment-over-prefix
    shape as packed verification, so the TPU hot path reuses
    ``kernels.paged_attention.paged_verify_attention`` (q_pos = chunk
    positions, owner = the row's blocks) instead of a dedicated
    chunk-prefill kernel.

    ``fused_cfg`` (a ``kernels/autotune.FusedConfig``, static) routes the
    read side through the fused Pallas kernel instead of the XLA gather;
    None keeps the gather formulation (bit-identical legacy path)."""
    num_blocks, bs = pool_dims(cache)
    if fused_cfg is not None:
        override = make_fused_decode_override(block_tables, num_blocks, bs,
                                              fused_cfg)
    else:
        override = make_paged_decode_override(block_tables, num_blocks, bs)
    return T.decode_step(params, cfg, cache, tokens=tokens, lengths=lengths,
                         segments=segments, opts=opts, attn_override=override)


def verify_step_paged(params, cfg, cache, *, tokens, positions, segments,
                      q_rows, block_tables, block_ids, block_owner,
                      q_anc=None, block_node=None, fused_cfg=None,
                      opts: T.Opts = T.Opts()):
    """Paged analogue of ``transformer.verify_step_packed``; optional
    ``q_anc``/``block_node`` add the token-tree topology mask term.
    ``fused_cfg`` selects the single-launch fused verify kernel (see
    :func:`decode_step_paged`)."""
    num_blocks, bs = pool_dims(cache)
    if fused_cfg is not None:
        override = make_fused_verify_override(
            q_rows, block_tables, block_ids, block_owner, num_blocks, bs,
            q_anc=q_anc, block_node=block_node, fused_cfg=fused_cfg)
    else:
        override = make_paged_verify_override(
            q_rows, block_tables, block_ids, block_owner, num_blocks, bs,
            q_anc=q_anc, block_node=block_node)
    return T.verify_step_packed(params, cfg, cache, tokens=tokens,
                                positions=positions, segments=segments,
                                attn_override=override, opts=opts)


def paged_compatible(cfg) -> bool:
    """Paged layout supports attention-family blocks (KV grids) only;
    recurrent state (mamba2/xlstm) is O(1) per request and sliding-window
    ring buffers have their own layout — both stay on the dense pool."""
    kinds = set(cfg.unit) | set(cfg.tail)
    return (kinds <= {C.ATTN, C.MOE, C.SHARED_ATTN}
            and not cfg.sliding_window)
