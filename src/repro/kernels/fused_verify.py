"""Fused paged verification kernel — one launch for the whole packed pass.

The serving engine's XLA path verifies a cohort in two HBM round-trips per
attention layer: an ``(M * bs,)`` gather materializes the live blocks as a
flat packed copy, then ``layers.attention`` reads that copy back.  This
kernel fuses the two: KV blocks stream **directly from the pool** through
the SMEM-prefetched block-id list (``PrefetchScalarGridSpec``), the
segment/position and tree ancestor-bitmask mask terms apply inline on each
tile, and an online softmax accumulates across tiles — the gathered copy
is never written, and per-layer launches drop from two to one.

On top of ``kernels/paged_attention.paged_verify_attention`` this kernel
adds the autotunable knobs searched by ``kernels/autotune.py``:

``bq``     query tile (rows of the packed query axis per grid step);
``bk``     KV sub-tile — the pool is viewed as ``(N * f, bk, Kh * D)``
           with ``f = bs // bk`` (a reshape, not a copy), so one physical
           block becomes ``f`` independently schedulable tiles;
``depth``  KV tiles fetched per grid step: the BlockSpec machinery issues
           the ``depth`` DMAs of step ``j+1`` while step ``j`` computes,
           i.e. block-table prefetch is double-buffered ``depth`` tiles
           ahead of the attention math.

Trailing grid steps (the power-of-two padding of ``block_ids``) clamp
their index map to the last *live* sub-block, so the revisit elides the
DMA (same trick as ``paged_decode_attention``) and ``pl.when`` skips the
compute — padding never costs a block read.  The block layout and the
per-head tile math are those of ``kernels/fused_decode.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_decode import attend_tile, finish_head, init_state


def _fused_verify_kernel(ids_ref, owner_ref, nlive_ref, q_lo_ref, q_hi_ref,
                         q_seg_ref, q_pos_ref, q_anc_ref, q_ref, *refs,
                         nsteps: int, depth: int, scale: float,
                         Kh: int, D: int, quantized: bool = False):
    group = 7 if quantized else 5
    tiles = refs[:group * depth]
    o_ref, m_ref, l_ref, acc_ref = refs[group * depth:]
    H = q_ref.shape[0]
    qi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    q_seg = q_seg_ref[...]                  # (BQ, 1)
    q_pos = q_pos_ref[...]
    q_anc = q_anc_ref[...]                  # (BQ, 1) ancestor bitmask

    def q_of(h):
        return q_ref[h].astype(jnp.float32) * scale         # (BQ, D)

    def _tile(i, pos_ref, seg_ref, node_ref, k_ref, v_ref, *sc_refs):
        t = j * depth + i
        owner = owner_ref[t]                # segment owning sub-block t

        # skip tiles past the live prefix and tiles owned by a segment
        # outside this query tile's [lo, hi] range
        @pl.when((t < nlive_ref[0]) & (owner >= 0)
                 & (owner >= q_lo_ref[qi]) & (owner <= q_hi_ref[qi]))
        def _compute():
            kv_pos = pos_ref[0]             # (1, bk)
            kv_node = node_ref[0]           # (1, bk) tree-node tag
            # a pool slot is attendable iff its block is live (owner >= 0)
            # and the slot itself holds committed/accepted KV (seg >= 0)
            kv_seg = jnp.where(seg_ref[0] >= 0, owner, -1)
            mask = (q_seg == kv_seg) & (kv_seg >= 0) & (kv_pos <= q_pos)
            # tree-topology term (see kernels/ref.tree_mask_term): -1 =
            # committed (always attendable), -2 = dead CoW duplicate
            # (never), n >= 0 = attendable iff bit n of the ancestor mask
            on_path = ((q_anc >> jnp.clip(kv_node, 0, 31)) & 1) == 1
            mask &= (kv_node == -1) | ((kv_node >= 0) & on_path)
            attend_tile(q_of, k_ref, v_ref, sc_refs, mask, m_ref, l_ref,
                        acc_ref, Kh=Kh, D=D, G=H // Kh)

    for i in range(depth):
        _tile(i, *tiles[group * i:group * (i + 1)])

    @pl.when(j == nsteps - 1)
    def _finish():
        for h in range(H):
            o_ref[h] = finish_head(l_ref, acc_ref, h).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "depth", "interpret"))
def fused_paged_verify(q, k_pool, v_pool, pool_seg, pool_pos,
                       q_seg, q_pos, block_ids, block_owner,
                       q_anc=None, block_node=None,
                       k_scale=None, v_scale=None, *,
                       bq: int = 128, bk: int = 0, depth: int = 1,
                       interpret: bool = False):
    """Single-launch packed verification streaming KV from the pool.

    Same contract as ``paged_attention.paged_verify_attention`` — q:
    (Tq, H, D); pools: (N, bs, Kh, D); pool_seg/pool_pos: (N, bs);
    q_seg/q_pos: (Tq,); block_ids/block_owner: (M,) live physical blocks
    and their owning segments (-1 owner = padding entry); optional
    q_anc (Tq,) / block_node (M, bs) tree topology.  Returns (Tq, H, D).

    ``bq``/``bk``/``depth`` are the autotuned tile knobs (module
    docstring); ``bk`` in (0, non-divisor of bs) falls back to ``bs``.

    k_scale/v_scale: optional (N, bs, Kh) float32 sidecars for quantized
    pools — KV tiles stream as int8/fp8 and are dequantized in-register
    (``scale * q``) before the mask/softmax math.
    """
    Tq, H, D = q.shape
    N, bs, Kh, _ = k_pool.shape
    M = block_ids.shape[0]
    if bk <= 0 or bs % bk:
        bk = bs
    depth = max(1, int(depth))
    f = bs // bk
    scale = 1.0 / np.sqrt(D)

    if q_anc is None:
        q_anc = jnp.full((Tq,), -1, jnp.int32)
    if block_node is None:
        block_node = jnp.full((M, bs), -1, jnp.int32)

    # sub-tile view of the pool — a reshape of contiguous memory, no copy
    quantized = k_scale is not None
    kp = k_pool.reshape(N * f, bk, Kh * D)
    vp = v_pool.reshape(N * f, bk, Kh * D)
    seg_p = pool_seg.astype(jnp.int32).reshape(N * f, 1, bk)
    pos_p = pool_pos.astype(jnp.int32).reshape(N * f, 1, bk)
    node_p = block_node.astype(jnp.int32).reshape(M * f, 1, bk)
    if quantized:
        ksp = k_scale.reshape(N * f, bk, Kh)
        vsp = v_scale.reshape(N * f, bk, Kh)

    ids = jnp.maximum(block_ids.astype(jnp.int32), 0)
    owner = block_owner.astype(jnp.int32)
    ids_sub = (ids[:, None] * f + jnp.arange(f)).reshape(M * f)
    owner_sub = jnp.repeat(owner, f)
    # live sub-blocks end at the last owner >= 0 entry (owner gaps inside
    # the live prefix, if any, stay untouched — only *trailing* padding
    # folds into revisits)
    last_live = jnp.max(jnp.where(owner >= 0,
                                  jnp.arange(M, dtype=jnp.int32), -1))
    nlive = ((last_live + 1) * f).reshape(1)

    nsteps = -(-(M * f) // depth)
    pad_t = nsteps * depth - M * f
    ids_sub = jnp.pad(ids_sub, (0, pad_t))
    owner_sub = jnp.pad(owner_sub, (0, pad_t), constant_values=-1)

    nq = -(-Tq // bq)
    Tq_p = nq * bq
    qp = jnp.pad(q, ((0, Tq_p - Tq), (0, 0), (0, 0))).transpose(1, 0, 2)

    def pad_col(x):
        return jnp.pad(x.astype(jnp.int32), (0, Tq_p - Tq),
                       constant_values=-1)[:, None]
    q_seg_p = pad_col(q_seg)
    q_pos_p = pad_col(q_pos)
    q_anc_p = pad_col(q_anc)
    # per query tile segment range, for the in-kernel owner skip
    q_lo = jnp.min(q_seg_p.reshape(nq, bq), axis=1)
    q_hi = jnp.max(q_seg_p.reshape(nq, bq), axis=1)

    def clamp(j, i, nl):
        # trailing steps revisit the last live sub-block: DMA elided,
        # compute skipped in-kernel via t < nlive
        return jnp.minimum(j * depth + i, jnp.maximum(nl[0], 1) - 1)

    def tile_map(i):
        return lambda qi, j, ids_s, ow, nl, lo, hi: \
            (ids_s[clamp(j, i, nl)], 0, 0)

    def node_map(i):
        # block_node is in *gathered* order, aligned with block_ids
        return lambda qi, j, ids_s, ow, nl, lo, hi: (clamp(j, i, nl), 0, 0)

    def col_map(qi, j, ids_s, ow, nl, lo, hi):
        return (qi, 0)

    def head_map(qi, j, ids_s, ow, nl, lo, hi):
        return (0, qi, 0)

    tile_specs = []
    tile_args = []
    for i in range(depth):
        tile_specs += [pl.BlockSpec((1, 1, bk), tile_map(i)),
                       pl.BlockSpec((1, 1, bk), tile_map(i)),
                       pl.BlockSpec((1, 1, bk), node_map(i)),
                       pl.BlockSpec((1, bk, Kh * D), tile_map(i)),
                       pl.BlockSpec((1, bk, Kh * D), tile_map(i))]
        tile_args += [pos_p, seg_p, node_p, kp, vp]
        if quantized:
            tile_specs += [pl.BlockSpec((1, bk, Kh), tile_map(i)),
                           pl.BlockSpec((1, bk, Kh), tile_map(i))]
            tile_args += [ksp, vsp]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nq, nsteps),
        in_specs=[
            pl.BlockSpec((bq, 1), col_map),
            pl.BlockSpec((bq, 1), col_map),
            pl.BlockSpec((bq, 1), col_map),
            pl.BlockSpec((H, bq, D), head_map),
        ] + tile_specs,
        out_specs=pl.BlockSpec((H, bq, D), head_map),
        scratch_shapes=[
            pltpu.VMEM((bq, H), jnp.float32),
            pltpu.VMEM((bq, H), jnp.float32),
            pltpu.VMEM((H, bq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_verify_kernel, nsteps=nsteps, depth=depth,
                          scale=scale, Kh=Kh, D=D, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Tq_p, D), q.dtype),
        interpret=interpret,
    )(ids_sub, owner_sub, nlive, q_lo, q_hi, q_seg_p, q_pos_p, q_anc_p, qp,
      *tile_args)
    return out.transpose(1, 0, 2)[:Tq]
