"""Fused paged decode kernel — per-row queries stream their block table.

The XLA decode path gathers each row's live blocks into a
``(B, nb_max * bs)`` copy and re-reads it through ``layers.attention``
(two HBM round-trips over the live KV per layer).  This kernel reads the
pool exactly once: the grid walks ``(row, kv tile)``, the KV BlockSpec
index map resolves logical sub-block -> physical through the
SMEM-prefetched block table before the DMA is issued, and segment /
position masking + online softmax run inline on each tile.

Unlike ``paged_attention.paged_decode_attention`` (single query token,
contiguous-prefix validity) this kernel carries the serving engine's full
decode shape: ``T`` query tokens per row (draft steps T=1, catch-up
T=W+1, chunked-prefill appends at the bucketed chunk width) with per-token
``q_seg``/``q_pos`` (seg -1 = bucket padding) and per-slot pool
``seg``/``pos`` validity — the exact semantics of
``serving/paged.make_paged_decode_override``, minus the gather copy.

Tile knobs (searched by ``kernels/autotune.py``): ``bk`` sub-tiles each
physical block (pool viewed as ``(N * f, bk, Kh * D)``), ``depth`` fetches
that many KV tiles per grid step so their DMAs double-buffer against the
previous tiles' attention compute.  Rows shorter than the longest row
clamp trailing steps to their last live sub-block — the revisit elides
the DMA and ``pl.when`` skips the compute, removing the per-step revisit
stalls of a padded dense walk.

Layout for the TPU compiler (Mosaic): every block's last two dimensions
are whole array dimensions, so no block shape depends on the (8, 128)
tile.  Queries go head-major ``(B, H, T, D)``; each KV tile arrives as
``(bk, Kh * D)`` and a head's keys are the lane slice ``[kh*D, (kh+1)*D)``;
per-token and per-slot metadata arrive as ``(T, 1)`` columns and
``(1, bk)`` rows, so every mask is a broadcast compare.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def attend_tile(q_of, k_ref, v_ref, sc_refs, mask, m_ref, l_ref, acc_ref,
                *, Kh: int, D: int, G: int):
    """Fold one KV tile into every head's online-softmax state.

    ``q_of(h)`` gives head h's scaled float32 queries ``(Tq, D)``;
    ``k_ref``/``v_ref`` hold the tile as ``(1, bk, Kh * D)``; ``sc_refs``
    is ``()`` or the ``(1, bk, Kh)`` int8/fp8 scale refs; ``mask`` is
    ``(Tq, bk)``.  State: ``m_ref``/``l_ref`` ``(Tq, H)`` running max and
    denominator, ``acc_ref`` ``(H, Tq, D)`` running numerator.  Shared by
    the fused decode and verify kernels."""
    for kh in range(Kh):
        lanes = slice(kh * D, (kh + 1) * D)
        k = k_ref[0, :, lanes].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, :, lanes].astype(jnp.float32)
        if sc_refs:
            ks_ref, vs_ref = sc_refs
            k = k * ks_ref[0, :, kh:kh + 1]
            v = v * vs_ref[0, :, kh:kh + 1]
        for g in range(G):
            h = kh * G + g
            s = jax.lax.dot_general(q_of(h), k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG)                      # (Tq, bk)
            m_prev = m_ref[:, h:h + 1]                       # (Tq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # the state starts at NEG, so a fully masked prefix keeps
            # m_safe at -1e29 and its correction factor underflows to 0
            m_safe = jnp.maximum(m_new, -1e29)
            p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
            corr = jnp.exp(m_prev - m_safe)
            l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * corr
                                 + jnp.sum(p, axis=-1, keepdims=True))
            m_ref[:, h:h + 1] = m_new
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)


def init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def finish_head(l_ref, acc_ref, h: int):
    """Head h's normalized output ``(Tq, D)``; rows that attended nothing
    are zero."""
    l = l_ref[:, h:h + 1]
    o = acc_ref[h] / jnp.maximum(l, 1e-30)
    return jnp.where(l > 0, o, 0.0)


def _fused_decode_kernel(bt_ref, nlive_ref, q_seg_ref, q_pos_ref, q_ref,
                         *refs, nsteps: int, depth: int, scale: float,
                         Kh: int, D: int, quantized: bool = False):
    group = 6 if quantized else 4
    tiles = refs[:group * depth]
    o_ref, m_ref, l_ref, acc_ref = refs[group * depth:]
    H = q_ref.shape[1]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    q_seg = q_seg_ref[0]                    # (T, 1)
    q_pos = q_pos_ref[0]

    def q_of(h):
        return q_ref[0, h].astype(jnp.float32) * scale      # (T, D)

    def _tile(i, pos_ref, seg_ref, k_ref, v_ref, *sc_refs):
        t = j * depth + i

        @pl.when(t < nlive_ref[b])
        def _compute():
            kv_seg = seg_ref[0]             # (1, bk) -1 = invalidated slot
            kv_pos = pos_ref[0]
            mask = (q_seg == kv_seg) & (kv_seg >= 0) & (kv_pos <= q_pos)
            attend_tile(q_of, k_ref, v_ref, sc_refs, mask, m_ref, l_ref,
                        acc_ref, Kh=Kh, D=D, G=H // Kh)

    for i in range(depth):
        _tile(i, *tiles[group * i:group * (i + 1)])

    @pl.when(j == nsteps - 1)
    def _finish():
        for h in range(H):
            o_ref[0, h] = finish_head(l_ref, acc_ref, h).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "depth", "interpret"))
def fused_paged_decode(q, k_pool, v_pool, pool_seg, pool_pos,
                       q_seg, q_pos, block_tables,
                       k_scale=None, v_scale=None, *,
                       bk: int = 0, depth: int = 1,
                       interpret: bool = False):
    """Multi-token paged decode streaming each row's blocks from the pool.

    q: (B, T, H, D); pools: (N, bs, Kh, D); pool_seg/pool_pos: (N, bs)
    per-slot validity (-1 = not attendable) and absolute position;
    q_seg/q_pos: (B, T) per-query segment (-1 = bucket padding, output
    ignored) and position; block_tables: (B, NB) physical block per
    logical block, -1 = unallocated (prefix-allocated per row).  Returns
    (B, T, H, D).  ``bk``/``depth`` as in ``fused_paged_verify``.

    k_scale/v_scale: optional (N, bs, Kh) float32 sidecars for quantized
    pools — each KV tile is dequantized in-register (``scale * q``) right
    after its DMA, under the same online softmax.
    """
    B, T, H, D = q.shape
    N, bs, Kh, _ = k_pool.shape
    NB = block_tables.shape[1]
    if bk <= 0 or bs % bk:
        bk = bs
    depth = max(1, int(depth))
    f = bs // bk
    scale = 1.0 / np.sqrt(D)

    quantized = k_scale is not None
    kp = k_pool.reshape(N * f, bk, Kh * D)
    vp = v_pool.reshape(N * f, bk, Kh * D)
    seg_p = pool_seg.astype(jnp.int32).reshape(N * f, 1, bk)
    pos_p = pool_pos.astype(jnp.int32).reshape(N * f, 1, bk)
    if quantized:
        ksp = k_scale.reshape(N * f, bk, Kh)
        vsp = v_scale.reshape(N * f, bk, Kh)

    bt = block_tables.astype(jnp.int32)
    bt_sub = (jnp.maximum(bt, 0)[:, :, None] * f
              + jnp.arange(f)).reshape(B, NB * f)
    # rows allocate blocks as a prefix, so the live sub-block count is
    # exact; rows with no blocks (idle pool rows) have nlive = 0 and every
    # tile skipped -> zero output, matching the XLA gather's full mask
    nlive = (jnp.sum(bt >= 0, axis=1) * f).astype(jnp.int32)

    nsteps = -(-(NB * f) // depth)
    pad_t = nsteps * depth - NB * f
    bt_sub = jnp.pad(bt_sub, ((0, 0), (0, pad_t)))

    def clamp(b, j, i, nl):
        return jnp.minimum(j * depth + i, jnp.maximum(nl[b], 1) - 1)

    def tile_map(i):
        return lambda b, j, bt_s, nl: \
            (bt_s[b, clamp(b, j, i, nl)], 0, 0)

    tile_specs = []
    tile_args = []
    for i in range(depth):
        tile_specs += [pl.BlockSpec((1, 1, bk), tile_map(i)),
                       pl.BlockSpec((1, 1, bk), tile_map(i)),
                       pl.BlockSpec((1, bk, Kh * D), tile_map(i)),
                       pl.BlockSpec((1, bk, Kh * D), tile_map(i))]
        tile_args += [pos_p, seg_p, kp, vp]
        if quantized:
            tile_specs += [pl.BlockSpec((1, bk, Kh), tile_map(i)),
                           pl.BlockSpec((1, bk, Kh), tile_map(i))]
            tile_args += [ksp, vsp]

    def row_map(b, j, bt_s, nl):
        return (b, 0, 0)

    def head_map(b, j, bt_s, nl):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nsteps),
        in_specs=[
            pl.BlockSpec((1, T, 1), row_map),
            pl.BlockSpec((1, T, 1), row_map),
            pl.BlockSpec((1, H, T, D), head_map),
        ] + tile_specs,
        out_specs=pl.BlockSpec((1, H, T, D), head_map),
        scratch_shapes=[
            pltpu.VMEM((T, H), jnp.float32),
            pltpu.VMEM((T, H), jnp.float32),
            pltpu.VMEM((H, T, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_decode_kernel, nsteps=nsteps, depth=depth,
                          scale=scale, Kh=Kh, D=D, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
    )(bt_sub, nlive, q_seg.astype(jnp.int32)[:, :, None],
      q_pos.astype(jnp.int32)[:, :, None], q.transpose(0, 2, 1, 3),
      *tile_args)
    return out.transpose(0, 2, 1, 3)
