"""Per-model KV-cache pools.

Two layouts share one interface (``has/insert/evict/rows/lengths/...``):

``PagedCachePool`` (default)
    KV lives in a physical *block pool* ``(num_blocks, block_size, Kh, D)``
    with a free-block list; each request owns an ordered block table.  The
    scheduler's KV budget *is* ``num_blocks`` — an enforced physical
    invariant, not a model.  Admission scatters the prefilled KV into
    exactly the prompt's blocks (O(prompt blocks), independent of pool
    capacity); decode growth appends one block at a time; eviction /
    preemption returns blocks to the free list in O(1) — no cache traffic.
    Rollback of rejected drafts trims the tail block in place (a
    ``gamma``-wide seg scatter).  Blocks carry copy-on-write refcounts:
    ``fork`` aliases a whole row in O(row blocks) with zero cache traffic,
    ``cow_prepare`` copies only the shared blocks a write is about to
    touch, and ``evict`` returns a block to the free list only when its
    last reference drops — the substrate for tree speculation, where every
    draft branch forks the main row and loses or wins in O(branches).
    ``kv_dtype`` in {bf16, int8, fp8} selects the block storage
    precision: quantized pools (kernels/quant.py) keep per-(slot, head)
    float32 scale sidecars inside each attention entry, written by the
    same scatters, copied by the same CoW block copy, and freed by the
    same refcount drop as the blocks they scale.
    Attention-only models (recurrent state is
    O(1)/request and stays dense); see ``serving/paged.py`` for how the
    model forward addresses the pool.

``DenseCachePool`` (legacy baseline)
    A fixed ``capacity x max_len`` batched grid; requests are inserted by
    functionally rewriting the whole tree (O(pool)) and every row
    physically reserves ``max_len`` cells whether used or not.  Kept as the
    baseline ``benchmarks/bench_paged.py`` measures against and as the
    layout for recurrent-state / sliding-window models.

Block-accounting invariant (property-tested in tests/test_paged.py):
``free_blocks + sum(blocks allocated to live rows) == num_blocks`` after
every admit/evict/preempt/ensure sequence — blocks can never leak.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quant
from repro.models import transformer as T


# ------------------------------------------------------------ dense layout --

def _row_set(pool_tree, row: int, one_tree):
    """Write a batch-1 cache into pool row `row`.  'scan' subtree leaves are
    (U, B, ...): batch axis 1; tail leaves are (B, ...): axis 0."""
    def go(pool_leaf, one_leaf, axis):
        idx = [slice(None)] * pool_leaf.ndim
        idx[axis] = row
        src_idx = [slice(None)] * one_leaf.ndim
        src_idx[axis] = 0
        return pool_leaf.at[tuple(idx)].set(one_leaf[tuple(src_idx)])

    out = {}
    for key, sub in pool_tree.items():
        axis = 1 if key == "scan" else 0
        out[key] = jax.tree.map(lambda p, o: go(p, o, axis), sub,
                                one_tree[key])
    return out


def _row_get(pool_tree, row: int):
    """Gather pool row `row` into a batch-1 cache (the inverse of
    ``_row_set``).  `row` is traced — one jitted trace serves every row.
    Used by the chunked-prefill append path: the engine runs a chunk's
    decode over the gathered row and scatters the result back."""
    def go(leaf, axis):
        idx = [slice(None)] * leaf.ndim
        idx[axis] = row
        return jnp.expand_dims(leaf[tuple(idx)], axis)

    out = {}
    for key, sub in pool_tree.items():
        axis = 1 if key == "scan" else 0
        out[key] = jax.tree.map(lambda l: go(l, axis), sub)
    return out


def _rows_invalidate(pool_tree, rows):
    """Mark attention slots of the given rows empty (seg=-1).  ``rows`` is
    a *traced* int array — one jitted trace serves every eviction batch;
    out-of-range entries (the fixed-width padding) are dropped by the
    scatter."""
    rows = jnp.asarray(rows)

    def fix(entry, stacked):
        if not (isinstance(entry, dict) and "seg" in entry):
            return entry
        out = dict(entry)
        if stacked:
            out["seg"] = entry["seg"].at[:, rows].set(-1)
        else:
            out["seg"] = entry["seg"].at[rows].set(-1)
        return out

    out = {}
    for key, sub in pool_tree.items():
        if key == "scan":
            out[key] = {k: fix(v, True) for k, v in sub.items()}
        else:
            out[key] = fix(sub, False)
    return out


class DenseCachePool:
    """Static (capacity, max_len) batched cache with request->row slots."""

    def __init__(self, cfg, capacity: int, max_len: int):
        self.cfg = cfg
        self.capacity = capacity
        self.max_len = max_len
        self.cache = T.init_cache(cfg, capacity, max_len)
        self.lengths = np.zeros(capacity, np.int64)
        self.last_token = np.zeros(capacity, np.int64)
        self.row_of: Dict[int, int] = {}
        self._free = list(range(capacity))
        self._row_set = jax.jit(_row_set)   # row is traced: no per-row retrace
        self._row_gather = jax.jit(_row_get)
        self._rows_inval = jax.jit(_rows_invalidate)

    def has(self, rid: int) -> bool:
        return rid in self.row_of

    @property
    def free_rows(self) -> int:
        return len(self._free)

    def can_admit(self, length: int) -> bool:
        return bool(self._free)

    def cells(self) -> Tuple[int, int, int]:
        """(used, held, alloc) KV cells: the live rows' lengths, the rows
        they hold at ``max_len`` each, and the whole grid."""
        used = int(sum(self.lengths[r] for r in self.row_of.values()))
        return (used, len(self.row_of) * self.max_len,
                self.capacity * self.max_len)

    def insert(self, rid: int, one_cache, length: int, last_token: int):
        row = self._free.pop()
        self.cache = self._row_set(self.cache, row, one_cache)
        self.row_of[rid] = row
        self.lengths[row] = length
        self.last_token[row] = last_token
        return row

    def insert_empty(self, rid: int) -> int:
        """Grant a row with no KV yet (chunked prefill: context arrives in
        append-chunk writes).  The row's slots are already seg-invalidated
        (fresh pool init / ``evict``), so nothing stale is attendable."""
        row = self._free.pop()
        self.row_of[rid] = row
        self.lengths[row] = 0
        self.last_token[row] = 0
        return row

    def row_cache(self, rid: int):
        """Batch-1 view of the request's row (gather, O(max_len))."""
        return self._row_gather(self.cache, self.row_of[rid])

    def write_row(self, rid: int, one_cache):
        """Scatter an updated batch-1 row back (append-chunk commit)."""
        self.cache = self._row_set(self.cache, self.row_of[rid], one_cache)

    def invalidate_rows(self, rows: List[int]):
        """Batched row invalidation: ONE jitted call for any number of rows
        (fixed width = capacity, padded with an out-of-range sentinel), so
        evicting k rows costs one tree update instead of k retraced ones."""
        if not rows:
            return
        arr = np.full(self.capacity, self.capacity, np.int32)
        arr[:len(rows)] = rows[:self.capacity]
        self.cache = self._rows_inval(self.cache, jnp.asarray(arr))

    def evict(self, rid: int):
        row = self.row_of.pop(rid)
        self.invalidate_rows([row])
        self.lengths[row] = 0
        self._free.append(row)

    def rows(self, rids) -> np.ndarray:
        return np.array([self.row_of[r] for r in rids], np.int32)


# backward-compat name (PR1 engine/docs referred to the dense pool as
# CachePool); the engine now picks the layout explicitly.
CachePool = DenseCachePool


# ------------------------------------------------------------ paged layout --

def _src_seq_len(one_cache) -> int:
    """Sequence capacity of a batch-1 dense cache (pool_dims sees it as
    one 'block' of that many slots)."""
    from repro.serving.paged import pool_dims
    return pool_dims(one_cache)[1]


def _map_attn_entries(pool_tree, fn):
    out = {}
    for key, sub in pool_tree.items():
        if key == "scan":
            out[key] = {k: fn(v, True, k) for k, v in sub.items()}
        else:
            out[key] = fn(sub, False, key)
    return out


def _blocks_write(pool_tree, one_tree, ids, *, nb: int, bs: int):
    """Scatter the first ``nb`` blocks of a batch-1 dense cache into the
    pool blocks ``ids`` (traced; out-of-range entries dropped).  Cost is
    O(nb * bs) regardless of pool size.  Quantized pools (entries carry
    ``k_scale``/``v_scale`` sidecars — kernels/quant.py) quantize K/V
    on the way in and scatter the scales into the same blocks."""
    def go(entry, stacked, name):
        src_e = one_tree["scan"][name] if stacked else one_tree[name]
        quantized = "k_scale" in entry

        def blocks(o):
            if stacked:                  # o: (U,1,S,...) -> (U,nb,bs,...)
                src = o[:, 0, :nb * bs]
                return src.reshape(src.shape[0], nb, bs, *src.shape[2:])
            return o[0, :nb * bs].reshape(nb, bs, *o.shape[2:])

        def put(p, src):
            if stacked:                  # p: (U,N,bs,...)
                return p.at[:, ids].set(src.astype(p.dtype))
            return p.at[ids].set(src.astype(p.dtype))

        out = dict(entry)
        for leaf in ("k", "v", "pos", "seg"):
            src = blocks(src_e[leaf])
            if quantized and leaf in ("k", "v"):
                q, sc = quant.quantize(src, entry[leaf].dtype)
                out[leaf] = put(entry[leaf], q)
                out[leaf + "_scale"] = put(entry[leaf + "_scale"], sc)
            else:
                out[leaf] = put(entry[leaf], src)
        return out
    return _map_attn_entries(pool_tree, go)


def _blocks_invalidate(pool_tree, ids):
    """seg = -1 over whole physical blocks (freshly re-allocated blocks may
    hold a prior owner's slots — they must never be attendable)."""
    def go(entry, stacked, name):
        out = dict(entry)
        if stacked:
            out["seg"] = entry["seg"].at[:, ids].set(-1)
        else:
            out["seg"] = entry["seg"].at[ids].set(-1)
        return out
    return _map_attn_entries(pool_tree, go)


def _blocks_copy(pool_tree, src, dst):
    """Copy whole physical blocks ``src[i] -> dst[i]`` (ALL leaves — K/V,
    pos/seg, and any quantization scale sidecars — all slots) — the
    copy-on-write materialisation.  Traced id vectors; padding entries
    carry an out-of-range dst and are dropped by the scatter (their src
    is clamped to a valid block by the gather)."""
    def go(entry, stacked, name):
        out = {}
        for leaf, p in entry.items():
            if stacked:
                out[leaf] = p.at[:, dst].set(p[:, src])
            else:
                out[leaf] = p.at[dst].set(p[src])
        return out
    return _map_attn_entries(pool_tree, go)


def _span_invalidate(pool_tree, table, new_lengths, upper, *, bs: int,
                     W: int, num_blocks: int):
    """Per-row seg=-1 for positions [new_lengths, upper) — the rejected-
    draft rollback.  W is the static span bound (gamma or gamma+1); rows
    whose table has no block there (idle rows) resolve out-of-range and the
    scatter drops them.  Trims the tail block in place: O(rows * W)."""
    cap, nbmax = table.shape
    p = new_lengths[:, None] + jnp.arange(W, dtype=jnp.int32)   # (cap, W)
    lb = p // bs
    phys = jnp.take_along_axis(table, jnp.clip(lb, 0, nbmax - 1), axis=1)
    ok = (p < upper[:, None]) & (lb < nbmax) & (phys >= 0)
    flat = jnp.where(ok, phys * bs + p % bs, num_blocks * bs).reshape(-1)

    def go(entry, stacked, name):
        seg = entry["seg"]
        out = dict(entry)
        if stacked:
            U = seg.shape[0]
            out["seg"] = seg.reshape(U, -1).at[:, flat].set(-1) \
                            .reshape(seg.shape)
        else:
            out["seg"] = seg.reshape(-1).at[flat].set(-1).reshape(seg.shape)
        return out
    return _map_attn_entries(pool_tree, go)


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class PagedCachePool:
    """Block-table paged KV pool (module docstring has the full contract)."""

    def __init__(self, cfg, capacity: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 kv_dtype: str = "bf16"):
        bs = int(block_size)
        if bs <= 0:
            raise ValueError("block_size must be positive")
        quant.storage_dtype(kv_dtype)                # validate the name
        self.cfg = cfg
        self.capacity = capacity
        self.block_size = bs
        self.kv_dtype = kv_dtype
        self.blocks_per_row = max(1, math.ceil(max_len / bs))
        self.max_len = self.blocks_per_row * bs      # block-aligned
        if num_blocks is None:
            num_blocks = capacity * self.blocks_per_row
        # floor: one full row must always fit (empty-pool admission of an
        # oversized request is unconditional — no deadlock)
        self.num_blocks = max(int(num_blocks), self.blocks_per_row)
        self.cache = T.init_paged_cache(cfg, self.num_blocks, bs,
                                        kv_dtype=kv_dtype)
        self.lengths = np.zeros(capacity, np.int64)
        self.last_token = np.zeros(capacity, np.int64)
        self.row_of: Dict[int, int] = {}
        self._free_rows = list(range(capacity))
        self._free_blocks = list(range(self.num_blocks))
        self._table = np.full((capacity, self.blocks_per_row), -1, np.int32)
        self._nb = np.zeros(capacity, np.int32)      # allocated blocks/row
        self._ref = np.zeros(self.num_blocks, np.int32)  # CoW refcounts
        self._jit: Dict[tuple, object] = {}          # (kind, statics) -> fn

    # --------------------------------------------------------- accounting --
    def has(self, rid: int) -> bool:
        return rid in self.row_of

    @property
    def free_rows(self) -> int:
        return len(self._free_rows)

    @property
    def free_blocks(self) -> int:
        return len(self._free_blocks)

    @property
    def allocated_blocks(self) -> int:
        # UNIQUE live blocks (a CoW-shared block counts once) so that
        # ``free_blocks + allocated_blocks == num_blocks`` stays an
        # identity under forking; fork-free this equals ``_nb.sum()``.
        return int(np.count_nonzero(self._ref))

    def ref_count(self, rid: int, block_index: int) -> int:
        """Refcount of the row's ``block_index``-th block (CoW probes)."""
        return int(self._ref[int(self._table[self.row_of[rid], block_index])])

    def shared_span(self, rid: int, start: int, end: int) -> bool:
        """True iff any block covering cells [start, end) is CoW-shared."""
        row = self.row_of[rid]
        bs = self.block_size
        lo = max(0, int(start)) // bs
        hi = min(int(self._nb[row]), math.ceil(max(int(end), 0) / bs))
        return any(self._ref[int(self._table[row, bi])] > 1
                   for bi in range(lo, hi))

    def bytes_per_block(self) -> int:
        """Physical bytes one block occupies across every layer's entry —
        K/V at the storage dtype, pos/seg, and quantization scale
        sidecars when present.  The currency for fixed-byte-budget
        comparisons across ``kv_dtype`` settings (benchmarks/
        bench_quant.py): at the same byte budget an int8 pool affords
        roughly 2x the blocks of a bf16 one (4x vs float32)."""
        total = sum(leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(self.cache))
        return total // self.num_blocks

    def bytes_per_token(self) -> int:
        """Physical bytes of KV state per cached token (all layers)."""
        return self.bytes_per_block() // self.block_size

    def blocks_needed(self, length: int) -> int:
        return min(self.blocks_per_row,
                   max(1, math.ceil(max(int(length), 1) / self.block_size)))

    def can_admit(self, length: int) -> bool:
        return (bool(self._free_rows)
                and len(self._free_blocks) >= self.blocks_needed(length))

    def allocated_cells(self, rid: int) -> int:
        return int(self._nb[self.row_of[rid]]) * self.block_size

    def cells(self) -> Tuple[int, int, int]:
        """(used, held, alloc) KV cells: the live rows' lengths, the
        blocks held (``num_blocks`` minus free) and every block, each at
        ``block_size`` cells."""
        used = int(sum(self.lengths[r] for r in self.row_of.values()))
        bs = self.block_size
        return (used, (self.num_blocks - len(self._free_blocks)) * bs,
                self.num_blocks * bs)

    def prefill_len(self, src_len: int) -> int:
        """Block-aligned cache length the engine should prefill with before
        ``insert`` — covers the (16-aligned) token row buffer so nothing is
        clamped, while keeping admission O(prompt blocks)."""
        bs = self.block_size
        return math.ceil(src_len / bs) * bs

    def rows(self, rids) -> np.ndarray:
        return np.array([self.row_of[r] for r in rids], np.int32)

    # ---------------------------------------------------------- lifecycle --
    def _fn(self, kind: str, **statics):
        key = (kind,) + tuple(sorted(statics.items()))
        if key not in self._jit:
            base = {"write": _blocks_write, "inval": _blocks_invalidate,
                    "span": _span_invalidate, "copy": _blocks_copy}[kind]
            fn = functools.partial(base, **statics) if statics else base
            # donate the pool tree: the scatter updates the block pool IN
            # PLACE instead of copying it — this is what makes admission
            # O(prompt blocks) instead of O(pool).  Callers always
            # reassign self.cache from the result, so the consumed buffer
            # is never reused.
            self._jit[key] = jax.jit(fn, donate_argnums=0)
        return self._jit[key]

    def _alloc(self, n: int) -> List[int]:
        if n > len(self._free_blocks):
            raise RuntimeError(
                f"paged pool out of blocks: need {n}, "
                f"free {len(self._free_blocks)}/{self.num_blocks} — the "
                f"scheduler's block accounting should have preempted first")
        ids = [self._free_blocks.pop() for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        return ids

    def insert(self, rid: int, one_cache, length: int, last_token: int):
        """Admit a prefilled batch-1 cache: allocate the prompt's blocks and
        scatter K/V into exactly those — O(prompt blocks), not O(pool)."""
        nb = self.blocks_needed(length)
        S = _src_seq_len(one_cache)
        if S < nb * self.block_size:
            raise ValueError(
                f"prefilled cache covers {S} slots < {nb} blocks x "
                f"{self.block_size}; prefill with max_len=pool.prefill_len()")
        ids = self._alloc(nb)
        self.cache = self._fn("write", nb=nb, bs=self.block_size)(
            self.cache, one_cache, jnp.asarray(ids, jnp.int32))
        row = self._free_rows.pop()
        self.row_of[rid] = row
        self._table[row, :nb] = ids
        self._nb[row] = nb
        self.lengths[row] = length
        self.last_token[row] = last_token
        return row

    def insert_empty(self, rid: int) -> int:
        """Grant a row that owns no blocks yet (chunked prefill: blocks are
        allocated chunk-by-chunk via ``ensure`` as context is appended)."""
        row = self._free_rows.pop()
        self.row_of[rid] = row
        self._nb[row] = 0
        self.lengths[row] = 0
        self.last_token[row] = 0
        return row

    def row_table(self, rid: int) -> jnp.ndarray:
        """(1, nb) block table of one row, power-of-two bucketed, for
        append-chunk writes through the paged decode override — chunk
        queries attend exactly this row's live blocks."""
        row = self.row_of[rid]
        nb = min(self.blocks_per_row, _pow2(max(1, int(self._nb[row]))))
        return jnp.asarray(self._table[row:row + 1, :nb])

    def ensure(self, rid: int, need_len: int):
        """Append blocks until the row covers ``need_len`` cells (the
        decode-growth path: usually one block, amortized zero)."""
        self.ensure_rows({rid: need_len})

    def ensure_rows(self, needs: Dict[int, int]):
        """Batched growth for one slot: allocate every row's missing
        blocks, then seg-invalidate all of them in ONE jitted call
        (re-allocated blocks may hold a prior owner's slots) — one
        dispatch per pool per slot, not one per grown row."""
        deltas = {}
        for rid, need_len in needs.items():
            row = self.row_of[rid]
            need = self.blocks_needed(need_len)
            if need > int(self._nb[row]):
                deltas[rid] = need
        total = sum(need - int(self._nb[self.row_of[rid]])
                    for rid, need in deltas.items())
        if not total:
            return
        if total > len(self._free_blocks):     # check before mutating
            raise RuntimeError(
                f"paged pool out of blocks: need {total}, "
                f"free {len(self._free_blocks)}/{self.num_blocks} — the "
                f"scheduler's block accounting should have preempted first")
        new_ids: List[int] = []
        for rid, need in deltas.items():
            row = self.row_of[rid]
            have = int(self._nb[row])
            ids = self._alloc(need - have)
            self._table[row, have:need] = ids
            self._nb[row] = need
            new_ids.extend(ids)
        m = _pow2(len(new_ids))               # bucket: bounded retraces
        arr = np.full(m, self.num_blocks, np.int32)
        arr[:len(new_ids)] = new_ids
        self.cache = self._fn("inval")(self.cache, jnp.asarray(arr))

    def fork(self, rid: int, new_rid: int) -> int:
        """Copy-on-write fork: grant ``new_rid`` a row whose block table
        ALIASES every block of ``rid`` — refcounts are bumped, no cache
        traffic moves.  Writes into the shared span must be preceded by
        ``cow_prepare`` (the write paths stay oblivious to sharing)."""
        if new_rid in self.row_of:
            raise ValueError(f"fork target rid {new_rid} already live")
        if not self._free_rows:
            raise RuntimeError("paged pool out of rows for fork")
        src = self.row_of[rid]
        row = self._free_rows.pop()
        nb = int(self._nb[src])
        self._table[row, :nb] = self._table[src, :nb]
        self._nb[row] = nb
        self.lengths[row] = self.lengths[src]
        self.last_token[row] = self.last_token[src]
        self.row_of[new_rid] = row
        for b in self._table[src, :nb]:
            self._ref[int(b)] += 1
        return row

    def cow_prepare(self, rid: int, start: int, end: int) -> int:
        """Make the blocks covering cells [start, end) exclusive to
        ``rid``: every CoW-shared block (ref > 1) in the span is copied
        into a freshly allocated block (one jitted whole-block copy for
        the batch), the row's table repointed, and the original's
        refcount dropped.  Returns the number of blocks copied."""
        row = self.row_of[rid]
        bs = self.block_size
        lo = max(0, int(start)) // bs
        hi = min(int(self._nb[row]), math.ceil(max(int(end), 0) / bs))
        src: List[int] = []
        dst: List[int] = []
        for bi in range(lo, hi):
            blk = int(self._table[row, bi])
            if self._ref[blk] > 1:
                new = self._alloc(1)[0]
                self._ref[blk] -= 1       # ref > 1, so never frees here
                self._table[row, bi] = new
                src.append(blk)
                dst.append(new)
        if src:
            m = _pow2(len(src))           # bucket: bounded retraces
            s = np.zeros(m, np.int32)
            d = np.full(m, self.num_blocks, np.int32)
            s[:len(src)] = src
            d[:len(dst)] = dst
            self.cache = self._fn("copy")(
                self.cache, jnp.asarray(s), jnp.asarray(d))
        return len(src)

    def rename(self, rid: int, new_rid: int):
        """Re-key a live row (winner-branch adoption after tree verify:
        the surviving fork takes over the original request id)."""
        if new_rid in self.row_of:
            raise ValueError(f"rename target rid {new_rid} already live")
        self.row_of[new_rid] = self.row_of.pop(rid)

    def evict(self, rid: int):
        """Free the row and drop one reference per block; blocks return
        to the free list only at refcount zero (CoW siblings keep shared
        blocks alive) — O(row blocks), no cache traffic (stale blocks are
        unreachable without a table entry and re-invalidated on
        re-allocation)."""
        row = self.row_of.pop(rid)
        nb = int(self._nb[row])
        for b in self._table[row, :nb]:
            b = int(b)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free_blocks.append(b)
        self._table[row, :nb] = -1
        self._nb[row] = 0
        self.lengths[row] = 0
        self._free_rows.append(row)

    def invalidate_span(self, new_lengths, upper, W: int):
        """Rollback rejected drafts: seg=-1 for positions
        [new_lengths, upper) per row (W = static span bound)."""
        table = jnp.asarray(self._table)
        self.cache = self._fn("span", bs=self.block_size, W=int(W),
                              num_blocks=self.num_blocks)(
            self.cache, table, jnp.asarray(new_lengths, jnp.int32),
            jnp.asarray(upper, jnp.int32))

    # ------------------------------------------------------------- views --
    def block_table_array(self) -> Tuple[jnp.ndarray, int]:
        """(capacity, nb_max) device block table, nb_max bucketed to the
        next power of two of the longest row's allocation — attention
        gathers scale with live context, and retraces stay O(log)."""
        nb_max = min(self.blocks_per_row,
                     _pow2(int(self._nb.max()) if len(self._nb) else 1))
        return jnp.asarray(self._table[:, :nb_max]), nb_max

    def live_blocks(self) -> Tuple[np.ndarray, np.ndarray]:
        """(block_ids, owner_rows) over all live rows, padded to a power-of
        -two length with (0, -1) entries (owner -1 = skip).  CoW-shared
        blocks are listed ONCE, under the first row encountered — listing
        a physical block twice would double its slots in the packed
        softmax denominator.  (Forks only share within one request, and
        all of a request's rows map to the same verify segment, so the
        first-seen owner is always segment-correct.)"""
        ids: List[int] = []
        owner: List[int] = []
        seen = set()
        for rid, row in self.row_of.items():
            nb = int(self._nb[row])
            for b in self._table[row, :nb]:
                b = int(b)
                if b in seen:
                    continue
                seen.add(b)
                ids.append(b)
                owner.append(row)
        m = _pow2(max(1, len(ids)))
        ids += [0] * (m - len(ids))
        owner += [-1] * (m - len(owner))
        return (np.asarray(ids, np.int32), np.asarray(owner, np.int32))
