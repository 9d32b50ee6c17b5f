"""Decoder-only LM assembly for every assigned architecture family.

The layer stack is a single ``lax.scan`` over repeating *units* (see
config.ModelConfig.unit) so full-size models lower to a small HLO even at 512
devices.  Caches are uniform: attention blocks carry a (B, S, Kh, hd) KV grid
plus per-slot absolute positions and segment ids (-1 = empty slot).  This one
representation supports ragged serving batches, sliding-window ring buffers,
and SPIN's packed/decomposed verification (segment-restricted softmax =
paper Eq. 13) without shape changes.

Entry points
  apply(...)            train / scoring forward over a full sequence
  prefill(...)          forward + cache construction
  decode_step(...)      one-token generation step (the dry-run ``serve_step``)
  make_train_step(...)  loss + AdamW update, remat/scan configurable
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import config as C
from repro.models import mamba2, moe, xlstm
from repro.models import params as pp
from repro.models.layers import (attention, embed, rms_norm, rope,
                                 softmax_cross_entropy, swiglu)
from repro.models.params import P
from repro.distributed.sharding import constrain


@dataclasses.dataclass(frozen=True)
class Opts:
    q_block: int = 512          # query-block size of chunked attention
    ssd_chunk: int = 128        # mamba2 / mlstm chunk length
    unroll_inner: bool = False  # unroll inner scans (roofline accounting mode)
    unroll_layers: bool = False # unroll the unit scan (roofline mode)
    remat: str = "full"         # full | dots | none  (train only)
    scan_layers: bool = True
    attn_stub: bool = False     # perf accounting: replace attention by a
                                # zero-cost stub (measures the attention
                                # subgraph's exact share of flops/bytes)


# ------------------------------------------------------------- param spec --

def _attn_spec(cfg: C.ModelConfig, is_moe: bool) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    # explicit 1/sqrt(fan_in): the default reads fan_in off shape[-2],
    # a head count for these head-split leaves.  Attention logits ~100x
    # too large make a deep random model chaotic: over 16 layers f32
    # reduction-order noise grows to whole logits, and no two attention
    # paths agree on a single token.
    s_in, s_out = d ** -0.5, (nq * hd) ** -0.5
    s: Dict[str, Any] = {
        "ln1": P((d,), ("embed",), init="zeros"),
        "wq": P((d, nq, hd), ("embed", "heads", "head_dim"), scale=s_in),
        "wk": P((d, nkv, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wv": P((d, nkv, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wo": P((nq, hd, d), ("heads", "head_dim", "embed"), scale=s_out),
        "ln2": P((d,), ("embed",), init="zeros"),
    }
    if cfg.qkv_bias:
        s["bq"] = P((nq, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = P((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = P((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
    if is_moe:
        s["router"] = P((d, cfg.n_experts), ("embed", None), scale=0.02)
        s["w_gate"] = P((cfg.n_experts, d, cfg.d_ff),
                        ("experts", "exp_embed", "mlp"))
        s["w_up"] = P((cfg.n_experts, d, cfg.d_ff),
                      ("experts", "exp_embed", "mlp"))
        s["w_down"] = P((cfg.n_experts, cfg.d_ff, d),
                        ("experts", "mlp", "exp_embed"))
    else:
        s["w_gate"] = P((d, cfg.d_ff), ("embed", "mlp"))
        s["w_up"] = P((d, cfg.d_ff), ("embed", "mlp"))
        s["w_down"] = P((cfg.d_ff, d), ("mlp", "embed"))
    return s


def _block_spec(cfg: C.ModelConfig, kind: str):
    if kind == C.ATTN:
        return _attn_spec(cfg, is_moe=False)
    if kind == C.MOE:
        return _attn_spec(cfg, is_moe=True)
    if kind == C.SHARED_ATTN:
        return {"ln1": P((cfg.d_model,), ("embed",), init="zeros")}  # see below
    if kind == C.MAMBA2:
        return mamba2.param_spec(cfg)
    if kind == C.MLSTM:
        return xlstm.mlstm_spec(cfg)
    if kind == C.SLSTM:
        return xlstm.slstm_spec(cfg)
    raise ValueError(kind)


def _stack_spec(spec, n: int):
    return jax.tree.map(
        lambda p: P((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale),
        spec, is_leaf=pp.is_leaf)


def param_spec(cfg: C.ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    spec: Dict[str, Any] = {}
    if cfg.embed_inputs:
        spec["embed"] = P((cfg.padded_vocab, d), ("vocab", "embed"), scale=0.02)
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        spec["lm_head"] = P((d, cfg.padded_vocab), ("embed", "vocab"))
    spec["final_norm"] = P((d,), ("embed",), init="zeros")

    unit = {}
    for i, kind in enumerate(cfg.unit):
        if kind == C.SHARED_ATTN:
            # per-application layernorms are private; weights shared (below)
            unit[f"u{i}_{kind}"] = _block_spec(cfg, kind)
        else:
            unit[f"u{i}_{kind}"] = _block_spec(cfg, kind)
    spec["scan"] = _stack_spec(unit, cfg.n_units)
    for i, kind in enumerate(cfg.tail):
        spec[f"tail{i}_{kind}"] = _block_spec(cfg, kind)
    if C.SHARED_ATTN in cfg.unit or C.SHARED_ATTN in cfg.tail:
        spec["shared_attn"] = _attn_spec(cfg, is_moe=False)
    return spec


def init_params(cfg, key, dtype=None):
    dtype = dtype or cfg.compute_dtype
    return pp.init_params(param_spec(cfg), key, dtype)


def abstract_params(cfg, dtype=None):
    dtype = dtype or cfg.compute_dtype
    return pp.abstract_params(param_spec(cfg), dtype)


def logical_axes(cfg):
    return pp.logical_axes(param_spec(cfg))


# ------------------------------------------------------------------ cache --

def cache_len(cfg: C.ModelConfig, max_len: int) -> int:
    if cfg.sliding_window:
        return min(max_len, cfg.sliding_window)
    return max_len


def _attn_cache_spec(cfg, batch, S):
    dt = cfg.compute_dtype
    Kh, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": ((batch, S, Kh, hd), dt),
        "v": ((batch, S, Kh, hd), dt),
        "pos": ((batch, S), jnp.int32),
        "seg": ((batch, S), jnp.int32),
    }


def _kind_cache(cfg, kind, batch, S, make):
    if kind in (C.ATTN, C.MOE, C.SHARED_ATTN):
        return {k: make(sh, dt) for k, (sh, dt)
                in _attn_cache_spec(cfg, batch, S).items()}
    if kind == C.MAMBA2:
        nh, hd, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * ds
        return mamba2.Mamba2State(
            ssd=make((batch, nh, hd, ds), jnp.float32),
            conv=make((batch, cfg.conv_kernel - 1, conv_dim),
                      cfg.compute_dtype))
    if kind == C.MLSTM:
        nh = cfg.n_heads
        dk = xlstm.PF_M * cfg.d_model // nh
        return xlstm.MLstmState(C=make((batch, nh, dk, dk), jnp.float32),
                                n=make((batch, nh, dk), jnp.float32))
    if kind == C.SLSTM:
        nh = cfg.n_heads
        hd = cfg.d_model // nh
        return xlstm.SLstmState(*[make((batch, nh, hd), jnp.float32)
                                  for _ in range(4)])
    raise ValueError(kind)


def _make_cache(cfg, batch, max_len, make):
    S = cache_len(cfg, max_len)

    def stacked(sh, dt):
        return make((cfg.n_units,) + sh, dt)

    cache: Dict[str, Any] = {"scan": {}}
    for i, kind in enumerate(cfg.unit):
        cache["scan"][f"u{i}_{kind}"] = _kind_cache(cfg, kind, batch, S,
                                                    stacked)
    for i, kind in enumerate(cfg.tail):
        cache[f"tail{i}_{kind}"] = _kind_cache(cfg, kind, batch, S, make)
    return cache


def init_cache(cfg, batch, max_len):
    def make(sh, dt):
        if dt == jnp.int32:
            return jnp.full(sh, -1, dt)   # seg/pos = -1 -> empty slot
        return jnp.zeros(sh, dt)
    return _make_cache(cfg, batch, max_len, make)


def _check_pageable(cfg):
    kinds = set(cfg.unit) | set(cfg.tail)
    bad = kinds - {C.ATTN, C.MOE, C.SHARED_ATTN}
    if bad:
        raise ValueError(f"paged KV needs attention-only models; {cfg.name} "
                         f"has recurrent-state blocks {sorted(bad)}")
    if cfg.sliding_window:
        raise ValueError("paged KV does not support sliding-window ring "
                         "buffers (window tail lives in the dense layout)")


def init_paged_cache(cfg, num_blocks, block_size, kv_dtype: str = "bf16"):
    """Paged KV block pool: same tree structure as ``init_cache`` but the
    leading cache axes are (physical block, slot-in-block) instead of
    (request row, position) — requests address it through block tables
    (serving/pool.py).  Attention-only models; see serving/paged.py.

    ``kv_dtype`` selects the block storage precision (kernels/quant.py):
    ``"bf16"`` keeps the compute dtype and the exact unquantized tree;
    ``"int8"``/``"fp8"`` store K/V quantized and add float32
    ``k_scale``/``v_scale`` sidecar leaves of shape
    ``(num_blocks, block_size, Kh)`` to every attention entry — indexed
    by the same block table as the blocks they scale."""
    from repro.kernels import quant
    _check_pageable(cfg)
    cache = init_cache(cfg, num_blocks, block_size)
    qdt = quant.storage_dtype(kv_dtype)
    if qdt is None:
        return cache

    def requant(entry):
        out = dict(entry)
        for leaf in ("k", "v"):
            out[leaf] = jnp.zeros(entry[leaf].shape, qdt)
            out[leaf + "_scale"] = jnp.zeros(entry[leaf].shape[:-1],
                                             jnp.float32)
        return out

    out = {"scan": {k: requant(v) for k, v in cache["scan"].items()}}
    for key, sub in cache.items():
        if key != "scan":
            out[key] = requant(sub)
    return out


def abstract_cache(cfg, batch, max_len):
    return _make_cache(cfg, batch, max_len,
                       lambda sh, dt: jax.ShapeDtypeStruct(sh, dt))


def cache_logical_axes(cfg, batch, max_len):
    """Logical-axis tree matching abstract_cache's structure (consumed by
    distributed/sharding.sharding_tree to build NamedShardings)."""
    attn_names = {
        4: ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
        2: ("cache_batch", "cache_seq"),
    }
    ssm_names = {
        4: ("cache_batch", "ssm_heads", None, None),          # ssd state
        3: ("cache_batch", None, "ssm_conv"),                 # conv history
    }

    def axes_for(kind, leaf_shape, stacked):
        nd = len(leaf_shape) - (1 if stacked else 0)
        if kind in (C.ATTN, C.MOE, C.SHARED_ATTN):
            base = attn_names[nd]
        elif kind == C.MAMBA2:
            base = ssm_names.get(nd, ("cache_batch",) + (None,) * (nd - 1))
        else:  # mlstm / slstm states: (B, nh, ...), heads shardable
            base = ("cache_batch", "heads") + (None,) * (nd - 2)
        return (("layers",) + base) if stacked else base

    ab = abstract_cache(cfg, batch, max_len)

    def walk(tree, kind, stacked):
        return jax.tree.map(lambda l: axes_for(kind, l.shape, stacked), tree)

    out = {"scan": {}}
    for i, kind in enumerate(cfg.unit):
        name = f"u{i}_{kind}"
        out["scan"][name] = walk(ab["scan"][name], kind, True)
    for i, kind in enumerate(cfg.tail):
        name = f"tail{i}_{kind}"
        out[name] = walk(ab[name], kind, False)
    return out


# ----------------------------------------------------------------- blocks --

def _project_qkv(p, h, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_block(p, x, cfg, opts, *, positions, segments, kv_cache,
                write_idx, is_moe, attend_cache=True, attn_override=None):
    """Returns (x_out, new_kv_cache, (moe_aux, moe_z)).

    kv_cache None              -> pure training forward (attend in-sequence)
    kv_cache, attend_cache=F   -> prefill: write K/V into the cache grid but
                                  attend over the full in-sequence K/V (the
                                  ring buffer only keeps the window tail)
    kv_cache, attend_cache=T   -> decode/verify: write at write_idx slots,
                                  attend over the whole cache grid.
    """
    B, S, d = x.shape
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(p, h, cfg, positions)
        segs = segments if segments is not None else jnp.zeros(
            (B, S), jnp.int32)

        new_cache = None
        if attn_override is not None:
            # SPIN packed verification: override handles attention and
            # the KV write-back
            o, new_cache = attn_override(q, k, v, positions, segs, kv_cache,
                                         cfg, opts)
        elif kv_cache is not None:
            bidx = jnp.arange(B)[:, None]
            kc = kv_cache["k"].at[bidx, write_idx].set(
                k.astype(kv_cache["k"].dtype))
            vc = kv_cache["v"].at[bidx, write_idx].set(
                v.astype(kv_cache["v"].dtype))
            pc = kv_cache["pos"].at[bidx, write_idx].set(positions)
            sc = kv_cache["seg"].at[bidx, write_idx].set(segs)
            new_cache = {"k": kc, "v": vc, "pos": pc, "seg": sc}

        if attn_override is not None:
            pass
        elif opts.attn_stub:
            # flash-accounting stub: keeps q/k/v projections + output shape,
            # removes the attention math (see benchmarks/perf_hillclimb.py)
            o = q * (jnp.mean(v) + jnp.mean(k))
        elif kv_cache is not None and attend_cache:
            o = attention(q, new_cache["k"], new_cache["v"],
                          q_positions=positions, kv_positions=new_cache["pos"],
                          q_segments=segs, kv_segments=new_cache["seg"],
                          window=cfg.sliding_window, q_block=opts.q_block)
        else:
            o = attention(q, k, v, q_positions=positions,
                          kv_positions=positions, q_segments=segments,
                          kv_segments=segments, window=cfg.sliding_window,
                          q_block=opts.q_block)
        o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        x = x + o
        x = constrain(x, "batch", "seq", "act_embed")

    aux = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if is_moe:
            hf = h.reshape(B * S, d)
            out, a, z = moe.moe_ffn(hf, p["router"], p["w_gate"],
                                    p["w_up"], p["w_down"], top_k=cfg.top_k,
                                    cf=cfg.capacity_factor)
            x = x + out.reshape(B, S, d)
            aux = (a, z)
        else:
            x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    x = constrain(x, "batch", "seq", "act_embed")
    return x, new_cache, aux


def _apply_kind(kind, p, x, cfg, opts, *, positions, segments, cache,
                write_idx, shared, attend_cache=True, attn_override=None):
    zero = (jnp.zeros((), jnp.float32),) * 2
    if kind in (C.ATTN, C.MOE, C.SHARED_ATTN):
        weights = shared if kind == C.SHARED_ATTN else p
        if kind == C.SHARED_ATTN:
            weights = dict(shared)
            weights["ln1"] = p["ln1"]   # private per-application norm
        x, new_cache, aux = _attn_block(
            weights, x, cfg, opts, positions=positions, segments=segments,
            kv_cache=cache, write_idx=write_idx, is_moe=(kind == C.MOE),
            attend_cache=attend_cache, attn_override=attn_override)
        return x, new_cache, aux
    if kind == C.MAMBA2:
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        out, st = mamba2.forward(p, h, cfg, state=cache,
                                 chunk=opts.ssd_chunk,
                                 unroll_inner=opts.unroll_inner)
        return constrain(x + out, "batch", "seq", "act_embed"), st, zero
    if kind == C.MLSTM:
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        out, st = xlstm.mlstm_forward(p, h, cfg, state=cache,
                                      chunk=opts.ssd_chunk,
                                      unroll_inner=opts.unroll_inner)
        return constrain(x + out, "batch", "seq", "act_embed"), st, zero
    if kind == C.SLSTM:
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        out, st = xlstm.slstm_forward(p, h, cfg, state=cache)
        return constrain(x + out, "batch", "seq", "act_embed"), st, zero
    raise ValueError(kind)


# ------------------------------------------------------------------ stack --

def _run_stack(params, x, cfg, opts, *, positions, segments, cache,
               write_idx, attend_cache=True, attn_override=None):
    """Run all units + tail. cache may be None (train).  Returns
    (x, new_cache_or_None, (aux_moe, aux_z))."""
    shared = params.get("shared_attn")
    want_cache = cache is not None

    def unit_body(carry, xs):
        x, am, az = carry
        p_unit, c_unit = xs
        new_c = {}
        for i, kind in enumerate(cfg.unit):
            name = f"u{i}_{kind}"
            c_in = c_unit[name] if want_cache else None
            x, c_out, (a, z) = _apply_kind(
                kind, p_unit[name], x, cfg, opts, positions=positions,
                segments=segments, cache=c_in, write_idx=write_idx,
                shared=shared, attend_cache=attend_cache,
                attn_override=attn_override)
            if want_cache:
                new_c[name] = c_out
            am, az = am + a, az + z
        return (x, am, az), (new_c if want_cache else 0)

    if opts.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if opts.remat == "dots" else None)
        unit_body = jax.checkpoint(unit_body, policy=policy,
                                   prevent_cse=not opts.scan_layers)

    zero = jnp.zeros((), jnp.float32)
    c_scan = cache["scan"] if want_cache else _dummy_scan_xs(cfg)
    if opts.scan_layers:
        (x, am, az), ys = lax.scan(
            unit_body, (x, zero, zero), (params["scan"], c_scan),
            unroll=cfg.n_units if opts.unroll_layers else 1)
        new_scan = ys if want_cache else None
    else:
        carry = (x, zero, zero)
        outs = []
        for u in range(cfg.n_units):
            xs_u = jax.tree.map(lambda t: t[u], (params["scan"], c_scan))
            carry, y = unit_body(carry, xs_u)
            outs.append(y)
        (x, am, az) = carry
        new_scan = (jax.tree.map(lambda *ts: jnp.stack(ts), *outs)
                    if want_cache else None)

    new_cache = {"scan": new_scan} if want_cache else None
    for i, kind in enumerate(cfg.tail):
        name = f"tail{i}_{kind}"
        c_in = cache[name] if want_cache else None
        x, c_out, (a, z) = _apply_kind(
            kind, params[name], x, cfg, opts, positions=positions,
            segments=segments, cache=c_in, write_idx=write_idx, shared=shared,
            attend_cache=attend_cache, attn_override=attn_override)
        if want_cache:
            new_cache[name] = c_out
        am, az = am + a, az + z
    return x, new_cache, (am, az)


def _dummy_scan_xs(cfg):
    # scan requires xs with a leading axis; use tiny zeros when no cache.
    return {f"u{i}_{k}": jnp.zeros((cfg.n_units,), jnp.float32)
            for i, k in enumerate(cfg.unit)}


# ------------------------------------------------------------ entrypoints --

def _inputs_to_x(cfg, params, tokens, inputs_embeds, prefix_embeds):
    if cfg.embed_inputs:
        x = embed(tokens, params["embed"]).astype(cfg.compute_dtype)
    else:
        x = inputs_embeds.astype(cfg.compute_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate(
            [prefix_embeds.astype(cfg.compute_dtype), x], axis=1)
    return x


def _logits(cfg, params, x):
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings and cfg.embed_inputs:
            return x @ params["embed"].T
        return x @ params["lm_head"]


def apply(params, cfg, *, tokens=None, inputs_embeds=None, prefix_embeds=None,
          positions=None, segments=None, opts: Opts = Opts()):
    """Full-sequence forward. Returns (logits, (moe_aux, moe_z))."""
    x = _inputs_to_x(cfg, params, tokens, inputs_embeds, prefix_embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, "batch", "seq", "act_embed")
    x, _, aux = _run_stack(params, x, cfg, opts, positions=positions,
                           segments=segments, cache=None, write_idx=None)
    logits = _logits(cfg, params, x)
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits, aux


def prefill(params, cfg, *, tokens=None, inputs_embeds=None,
            prefix_embeds=None, lengths=None, max_len=None, segments=None,
            positions=None, last_logits_only=False, opts: Opts = Opts()):
    """Process prompts, build cache.  Returns (logits, cache).

    lengths: (B,) valid prompt lengths (tokens beyond are padding).
    max_len: cache capacity (defaults to prompt length + 0 slack).
    """
    x = _inputs_to_x(cfg, params, tokens, inputs_embeds, prefix_embeds)
    B, S, _ = x.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if segments is None:
        segments = jnp.where(positions < lengths[:, None], 0, -1)
    max_len = max_len or S
    cache = init_cache(cfg, B, max_len)
    Sc = cache_len(cfg, max_len)
    if cfg.sliding_window and Sc < S:
        # ring buffer: only the last Sc positions land in the cache; earlier
        # ones are redirected out of bounds (scatter drops OOB updates).
        write_idx = jnp.where(positions >= S - Sc, positions % Sc, Sc)
    else:
        write_idx = jnp.minimum(positions, Sc - 1)
    x = constrain(x, "batch", "seq", "act_embed")
    x, cache, aux = _run_stack(params, x, cfg, opts, positions=positions,
                               segments=segments, cache=cache,
                               write_idx=write_idx, attend_cache=False)
    if last_logits_only:
        # gather each row's last valid position BEFORE the lm head so the
        # (B, S, vocab) logits tensor is never materialized (32k prefill).
        idx = jnp.maximum(lengths - 1, 0)
        x = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32)
                                .repeat(x.shape[-1], -1), axis=1)
    logits = _logits(cfg, params, x)
    return logits, cache


def decode_step(params, cfg, cache, *, tokens=None, inputs_embeds=None,
                lengths=None, segments=None, attn_override=None,
                opts: Opts = Opts()):
    """One generation step. tokens: (B, T) with T new tokens per row (T=1 for
    plain serving; T=gamma+1 for SPIN verification rows).
    lengths: (B,) current context length per row.  Returns (logits, cache).
    attn_override (optional) replaces attention + KV write-back per layer —
    the paged-KV path (serving/paged.py) routes block tables through it."""
    x = _inputs_to_x(cfg, params, tokens, inputs_embeds, None)
    B, T, _ = x.shape
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    any_attn = bool(cfg.attn_positions)
    Sc = None
    if any_attn:
        # cache capacity from any attention entry
        for i, kind in enumerate(cfg.unit):
            if kind in (C.ATTN, C.MOE, C.SHARED_ATTN):
                Sc = cache["scan"][f"u{i}_{kind}"]["k"].shape[2]
                break
        if Sc is None:
            for i, kind in enumerate(cfg.tail):
                if kind in (C.ATTN, C.MOE, C.SHARED_ATTN):
                    Sc = cache[f"tail{i}_{kind}"]["k"].shape[1]
                    break
    if Sc is not None:
        write_idx = positions % Sc if cfg.sliding_window else positions
    else:
        write_idx = positions
    if segments is None:
        segments = jnp.zeros((B, T), jnp.int32)
    x = constrain(x, "batch", "seq", "act_embed")
    x, cache, _ = _run_stack(params, x, cfg, opts, positions=positions,
                             segments=segments, cache=cache,
                             write_idx=write_idx, attn_override=attn_override)
    logits = _logits(cfg, params, x)
    return logits, cache


def verify_step_packed(params, cfg, cache, *, tokens, positions, segments,
                       attn_override, opts: Opts = Opts()):
    """SPIN packed verification: all requests' query tokens flattened into
    one (1, Tq) row; attention and cache write-back are handled by the
    decompose.make_attn_override closure.  Returns (logits, cache)."""
    x = _inputs_to_x(cfg, params, tokens, None, None)
    x = constrain(x, "batch", "seq", "act_embed")
    x, cache, _ = _run_stack(params, x, cfg, opts, positions=positions,
                             segments=segments, cache=cache,
                             write_idx=None, attn_override=attn_override)
    logits = _logits(cfg, params, x)
    return logits, cache


# -------------------------------------------------------------- train step --

def loss_fn(params, cfg, batch, opts: Opts = Opts()):
    logits, (aux, z) = apply(
        params, cfg, tokens=batch.get("tokens"),
        inputs_embeds=batch.get("inputs_embeds"),
        prefix_embeds=batch.get("prefix_embeds"), opts=opts)
    labels = batch["labels"]
    if "prefix_embeds" in batch and batch["prefix_embeds"] is not None:
        Ppre = batch["prefix_embeds"].shape[1]
        logits = logits[:, Ppre:]
    # next-token prediction: logits[t] predicts labels[t]
    loss = softmax_cross_entropy(logits, labels, batch.get("mask"),
                                 cfg.vocab_size)
    total = loss + 0.01 * aux + 1e-3 * z
    return total, {"loss": loss, "moe_aux": aux, "moe_z": z}


def make_train_step(cfg, optimizer, opts: Opts = Opts()):
    def train_step(params, opt_state, batch):
        grad_fn = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, opts), has_aux=True)
        (total, metrics), grads = grad_fn(params)
        params, opt_state = optimizer.update(params, grads, opt_state)
        metrics["total"] = total
        return params, opt_state, metrics
    return train_step
