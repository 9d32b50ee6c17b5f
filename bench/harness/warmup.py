"""Warm every program the window can reach, before the window.

Two kinds of shapes occur.  Admission programs (the target's and the
drafters' prefill, the pool writes, the first-token pick) take one
shape per prompt length bucket: the traffic has a fixed set of prompt
lengths, and one short request of each is served through the engine.
Step programs (packed verify, the drafters' decode and catch-up, the
pools' block invalidation) take one shape per power-of-two bucket of
the pools' block tables and live blocks: the engine's own entry points
are called once for every bucket the pool's geometry allows, with
arguments built as the engine builds them and nothing attendable, and
their results are dropped."""

from __future__ import annotations

import numpy as np

WARM_RID = 1 << 40


def pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def table_buckets(bpr: int):
    """The block-table widths a pool of ``bpr`` blocks per row can show:
    powers of two, capped at ``bpr``."""
    return sorted({min(bpr, pow2(k)) for k in range(1, bpr + 1)})


def live_buckets(nb: int, capacity: int, num_blocks: int):
    """Power-of-two counts of live blocks possible while the longest row
    has a table of width ``nb``: at least that row's blocks, at most
    every row as long as it."""
    lo = pow2(nb // 2 + 1) if nb > 1 else 1
    hi = pow2(min(capacity * nb, num_blocks))
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def serve_sizes(sess):
    """Serve one short request of every prompt length in the mix, a
    pool's worth at a time, through the engine's normal entry points."""
    from repro.data.workloads import Request
    eng = sess.engine
    lengths = sorted({int(p) for p, _ in sess.schedule.sizes})
    cap = eng.ecfg.capacity
    rng = np.random.default_rng([sess.seed, 4])
    for i in range(0, len(lengths), cap):
        reqs = [Request(rid=WARM_RID + i + j, dataset="warm", difficulty=0.0,
                        prompt=rng.integers(0, sess.target.sizes.vocab, L)
                        .astype(np.int32), max_new=1)
                for j, L in enumerate(lengths[i:i + cap])]
        eng.add_requests(reqs)
        while any(not r.done for r in reqs):
            eng.step()


def warm_steps(eng):
    """Call the step programs once for every bucket the pools allow."""
    import jax
    import jax.numpy as jnp
    from repro.core import decompose as D
    W = eng.gamma_max
    pool = eng.llm_pool
    N = pool.capacity
    lens = np.zeros(N, np.int64)
    q_rows, q_pos, q_seg = D.build_query_layout(lens, W)
    tokens = jnp.zeros((N, W + 1), jnp.int32).reshape(1, -1)
    outs = []
    for nb in table_buckets(pool.blocks_per_row):
        bt = jnp.asarray(np.full((N, nb), -1, np.int32))
        for m in live_buckets(nb, N, pool.num_blocks):
            outs.append(eng.llm.verify_paged(
                pool.cache, tokens, jnp.asarray(q_pos.astype(np.int32)),
                jnp.asarray(q_seg), jnp.asarray(q_rows), bt,
                jnp.asarray(np.zeros(m, np.int32)),
                jnp.asarray(np.full(m, -1, np.int32)), eng.fused_cfg)[0])
            jax.block_until_ready(outs.pop())
    for b, sp in zip(eng.ssms, eng.ssm_pools):
        n = sp.capacity
        length = jnp.asarray(np.zeros(n, np.int64), jnp.int32)
        for nb in table_buckets(sp.blocks_per_row):
            bt = jnp.asarray(np.full((n, nb), -1, np.int32))
            for t in (1, W + 1):
                out = b.decode_paged(sp.cache, jnp.zeros((n, t), jnp.int32),
                                     length + 1, bt, eng.fused_cfg)[0]
                jax.block_until_ready(out)
    for p in [pool, *eng.ssm_pools]:
        m = 1
        while m <= pow2(max(p.capacity, p.blocks_per_row)):
            arr = np.full(m, p.num_blocks, np.int32)
            p.cache = p._fn("inval")(p.cache, jnp.asarray(arr))
            m *= 2
        jax.block_until_ready(p.cache)


def warm(sess):
    serve_sizes(sess)
    warm_steps(sess.engine)
