"""Config autotuner for the fused speculative-step kernels.

The fused kernels (``kernels/fused_verify.py``, ``kernels/fused_decode.py``)
expose three tile knobs — query tile ``bq`` (verify only), KV sub-tile
``bk`` and prefetch ``depth``.  The right choice depends on the model's
attention geometry and the paging granularity, so this module benchmarks
the small candidate grid on synthetic pool shapes and caches the winner
per tune key::

    (kind | H x Kh x D | gamma_max | block_size | linear/tree | kv dtype
     | backend)

The kv dtype component keeps int8/fp8 winners (half the KV bytes per
tile, dequant multiply in the inner loop) from colliding with bf16
entries for the same geometry; keys written before the component existed
are migrated to ``kvbf16`` on load and malformed keys are dropped.

Winners persist in ``results/TUNE_cache.json``.  ``kernels/ops.py``
consults :func:`get_config` at dispatch when no explicit config is given;
the serving engine resolves its configs once at construction.  A cache
miss NEVER tunes implicitly (tuning runs kernels; dispatch must stay
cheap and deterministic) — it falls back to :data:`DEFAULT_CONFIG`, and
``CACHE_STATS`` records the miss so benchmarks can report coverage.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

CACHE_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "results", "TUNE_cache.json")
ROOFLINE_PATH = os.path.join(os.path.dirname(CACHE_PATH),
                             "dryrun_baseline.json")

# current key grammar (see tune_key); legacy = same minus the kv field
_KEY_FIELDS = (r"(verify|decode)", r"H\d+xKh\d+xD\d+", r"g\d+", r"bs\d+",
               r"(linear|tree)", r"kv\w+", r"\w+")
_KEY_RE = re.compile("^" + r"\|".join(_KEY_FIELDS) + "$")
_LEGACY_RE = re.compile(
    "^" + r"\|".join(_KEY_FIELDS[:5] + _KEY_FIELDS[6:]) + "$")

# consult/miss counters, reset-able by benchmarks and tests
CACHE_STATS: Dict[str, int] = {"hits": 0, "misses": 0}


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """Tile config of one fused kernel launch.  Frozen (hashable) so jit
    caches can key on it.  ``bk = 0`` means "one tile per physical block"
    (the kernels also fall back to that when bk does not divide bs)."""
    bq: int = 128
    bk: int = 0
    depth: int = 1


DEFAULT_CONFIG = FusedConfig()


def tune_key(kind: str, *, H: int, Kh: int, D: int, gamma_max: int,
             block_size: int, shape: str = "linear",
             kv_dtype: str = "bf16") -> str:
    """Cache key: kernel kind + model attention geometry + speculation
    depth cap + paging granularity + linear/tree + kv storage dtype +
    backend (tile trade-offs differ between compiled Mosaic and the CPU
    interpreter)."""
    return (f"{kind}|H{H}xKh{Kh}xD{D}|g{gamma_max}|bs{block_size}"
            f"|{shape}|kv{kv_dtype}|{jax.default_backend()}")


def _migrate_key(key: str) -> Optional[str]:
    """Current keys pass through; pre-kv-dtype keys (written by older
    tuners, necessarily bf16 pools) gain ``kvbf16``; anything else is
    corrupt and dropped (returns None)."""
    if _KEY_RE.match(key):
        return key
    if _LEGACY_RE.match(key):
        head, backend = key.rsplit("|", 1)
        return f"{head}|kvbf16|{backend}"
    return None


def load_cache(path: Optional[str] = None) -> dict:
    path = path or CACHE_PATH
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if not isinstance(raw, dict):
        return {}
    # current-format keys win over a legacy key migrating to the same slot
    cache = {k: v for k, v in raw.items()
             if _KEY_RE.match(k) and isinstance(v, dict)}
    for key, entry in raw.items():
        mig = _migrate_key(key)
        if mig is not None and mig != key and isinstance(entry, dict):
            cache.setdefault(mig, entry)
    return cache


def save_cache(cache: dict, path: Optional[str] = None) -> None:
    path = path or CACHE_PATH
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)


def lookup(key: str, path: Optional[str] = None) -> Optional[FusedConfig]:
    """Cached winner for ``key``, or None (counted in CACHE_STATS)."""
    entry = load_cache(path).get(key)
    if entry is None:
        CACHE_STATS["misses"] += 1
        return None
    CACHE_STATS["hits"] += 1
    return FusedConfig(bq=int(entry.get("bq", DEFAULT_CONFIG.bq)),
                       bk=int(entry.get("bk", DEFAULT_CONFIG.bk)),
                       depth=int(entry.get("depth", DEFAULT_CONFIG.depth)))


def get_config(kind: str, *, H: int, Kh: int, D: int, gamma_max: int = 0,
               block_size: int = 0, shape: str = "linear",
               kv_dtype: str = "bf16",
               path: Optional[str] = None) -> FusedConfig:
    """Dispatch-time lookup with the safe default fallback."""
    cfg = lookup(tune_key(kind, H=H, Kh=Kh, D=D, gamma_max=gamma_max,
                          block_size=block_size, shape=shape,
                          kv_dtype=kv_dtype), path)
    return cfg if cfg is not None else DEFAULT_CONFIG


def roofline_candidates(kind: str, block_size: int,
                        path: Optional[str] = None) -> List[FusedConfig]:
    """Extra grid points derived from the dry-run roofline records
    (``results/dryrun_baseline.json``, the table benchmarks/roofline.py
    renders).  Memory-bound arches reward deeper DMA pipelining and
    smaller KV sub-tiles (more overlap windows per block); compute-bound
    ones reward a wider query tile amortizing each streamed block over
    more rows.  Missing/empty file -> no extra candidates (the static
    grid stands alone)."""
    try:
        with open(path or ROOFLINE_PATH) as f:
            records = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []
    doms = set()
    for rec in records if isinstance(records, list) else []:
        if not isinstance(rec, dict):
            continue
        rf = rec.get("roofline") or {}
        if rec.get("status", "ok") == "ok" and rf.get("dominant"):
            doms.add(rf["dominant"])
    out = []
    if "memory" in doms:
        bk = (block_size // 4
              if block_size % 4 == 0 and block_size // 4 >= 8 else 0)
        out += [FusedConfig(bq=DEFAULT_CONFIG.bq, bk=bk, depth=d)
                for d in (3, 4)]
    if ("compute" in doms or "collective" in doms) and kind == "verify":
        out.append(FusedConfig(bq=256, bk=0, depth=1))
    return out


def candidate_configs(kind: str, block_size: int,
                      roofline_path: Optional[str] = None) \
        -> List[FusedConfig]:
    """Small search grid: bq tiles at/below the common packed widths, bk
    halving down to 8 slots, depth 1 (pure pipelining) or 2 (explicit
    double-buffer), plus any roofline-derived points for this machine's
    dry-run profile.  Kept deliberately tiny — tuning runs kernels."""
    bks = [0]
    if block_size % 2 == 0 and block_size // 2 >= 8:
        bks.append(block_size // 2)
    bqs = [128, 32] if kind == "verify" else [0]
    out = []
    for bq in bqs:
        for bk in bks:
            for depth in (1, 2):
                out.append(FusedConfig(bq=bq or DEFAULT_CONFIG.bq, bk=bk,
                                       depth=depth))
    for cfg in roofline_candidates(kind, block_size, roofline_path):
        if cfg not in out:
            out.append(cfg)
    return out


def _median_us(fn, iters: int = 5, warmup: int = 1) -> float:
    ts = []
    for _ in range(iters + warmup):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts[warmup:]))


def _synthetic_pool(H, Kh, D, gamma_max, block_size, seed=0):
    """Tiny but representative paged state: 4 rows, 2 blocks each, the
    speculation window of the last row half-written."""
    rng = np.random.default_rng(seed)
    bs = block_size
    B, nb = 4, 2
    N = B * nb + 2                                     # + free blocks
    k_pool = jnp.asarray(rng.standard_normal((N, bs, Kh, D)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((N, bs, Kh, D)), jnp.float32)
    bt = np.full((B, nb), -1, np.int32)
    seg = np.full((N, bs), -1, np.int32)
    pos = np.zeros((N, bs), np.int32)
    ids, owner = [], []
    ctx = bs + max(2, bs // 2)                         # straddles 2 blocks
    for b in range(B):
        for lb in range(nb):
            blk = b * nb + lb
            bt[b, lb] = blk
            ids.append(blk)
            owner.append(b)
            lo = lb * bs
            n = int(np.clip(ctx - lo, 0, bs))
            seg[blk, :n] = 0
            pos[blk] = lo + np.arange(bs)
    m = 1 << (len(ids) - 1).bit_length()
    ids += [0] * (m - len(ids))
    owner += [-1] * (m - len(owner))
    W = max(1, gamma_max)
    lens = np.full(B, ctx, np.int64)
    return dict(k_pool=k_pool, v_pool=v_pool,
                pool_seg=jnp.asarray(seg), pool_pos=jnp.asarray(pos),
                bt=jnp.asarray(bt), ids=jnp.asarray(np.asarray(ids,
                                                               np.int32)),
                owner=jnp.asarray(np.asarray(owner, np.int32)),
                lens=lens, W=W, B=B, rng=rng)


def autotune(kind: str, *, H: int, Kh: int, D: int, gamma_max: int,
             block_size: int, shape: str = "linear",
             kv_dtype: str = "bf16",
             path: Optional[str] = None, seed: int = 0) -> FusedConfig:
    """Benchmark the candidate grid for one tune key, persist and return
    the winner.  Safe to re-run (overwrites the entry).  Quantized
    ``kv_dtype`` tunes against int8/fp8 synthetic pools with scale
    sidecars, so the winner reflects the dequant inner loop."""
    from repro.kernels import quant
    from repro.kernels.fused_decode import fused_paged_decode
    from repro.kernels.fused_verify import fused_paged_verify
    from repro.kernels.ops import interpret_mode

    syn = _synthetic_pool(H, Kh, D, gamma_max, block_size, seed)
    B, W, rng = syn["B"], syn["W"], syn["rng"]
    interpret = interpret_mode()
    k_scale = v_scale = None
    qdt = quant.storage_dtype(kv_dtype)
    if qdt is not None:
        syn["k_pool"], k_scale = quant.quantize(syn["k_pool"], qdt)
        syn["v_pool"], v_scale = quant.quantize(syn["v_pool"], qdt)

    if kind == "verify":
        Tq = B * (W + 1)
        q = jnp.asarray(rng.standard_normal((Tq, H, D)), jnp.float32)
        q_seg = jnp.repeat(jnp.arange(B, dtype=jnp.int32), W + 1)
        q_pos = jnp.asarray(
            np.concatenate([syn["lens"][b] + np.arange(W + 1)
                            for b in range(B)]).astype(np.int32))
        anc = (jnp.full((Tq,), -1, jnp.int32) if shape == "tree" else None)
        node = (jnp.full((syn["ids"].shape[0], block_size), -1, jnp.int32)
                if shape == "tree" else None)

        def run(cfg):
            return fused_paged_verify(
                q, syn["k_pool"], syn["v_pool"], syn["pool_seg"],
                syn["pool_pos"], q_seg, q_pos, syn["ids"], syn["owner"],
                anc, node, k_scale, v_scale,
                bq=cfg.bq, bk=cfg.bk, depth=cfg.depth,
                interpret=interpret)
    elif kind == "decode":
        T = W + 1
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        q_seg = jnp.zeros((B, T), jnp.int32)
        q_pos = jnp.asarray(syn["lens"][:, None]
                            + np.arange(T)[None], jnp.int32)

        def run(cfg):
            return fused_paged_decode(
                q, syn["k_pool"], syn["v_pool"], syn["pool_seg"],
                syn["pool_pos"], q_seg, q_pos, syn["bt"],
                k_scale, v_scale,
                bk=cfg.bk, depth=cfg.depth, interpret=interpret)
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")

    best, best_us = None, float("inf")
    for cfg in candidate_configs(kind, block_size):
        us = _median_us(lambda: run(cfg))
        if us < best_us:
            best, best_us = cfg, us
    key = tune_key(kind, H=H, Kh=Kh, D=D, gamma_max=gamma_max,
                   block_size=block_size, shape=shape, kv_dtype=kv_dtype)
    cache = load_cache(path)
    cache[key] = {"bq": best.bq, "bk": best.bk, "depth": best.depth,
                  "us": round(best_us, 1),
                  "candidates": len(candidate_configs(kind, block_size))}
    save_cache(cache, path)
    return best
