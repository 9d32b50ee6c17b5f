"""The plain reference: a Qwen2 forward pass in float32 at the highest
matmul precision, written from the published architecture (RMSNorm,
rotary embeddings over halves, grouped-query attention with QKV bias,
SwiGLU MLP, tied or untied head).  It imports nothing of the program and
makes its own weights again from the seed (``harness.weights``), one
layer at a time inside the loop, so it holds no more than one layer's
weights.  Attention runs in blocks of queries and the head in blocks of
positions, so a batch of long sequences fits beside nothing else.

``gaps`` teacher-forces the served tokens: at each scored position it
returns the reference's best logit minus its logit for the token that
followed there (0 where the served token is the reference's best).
With ``control`` it runs a second forward whose matrices are rounded to
float8 (e4m3, one scale per output column) and returns, beside the
gaps, the reference's gap for the token the float8 forward puts first:
what a server in that precision would have served."""

from __future__ import annotations

import functools

from harness.weights import Qwen2, global_weights, layer_weights

Q_BLOCK = 256
ROW_BLOCK = 256
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _fp8_round(w):
    """Round a float32 matrix (input-major) to float8 e4m3 with one
    scale per output column, and back to float32."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _forward_layer(model: Qwen2, w: dict, x, positions):
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision.HIGHEST
    B, S, d = x.shape
    nq, nkv, hd = model.heads, model.kv_heads, model.head_dim
    G = nq // nkv

    def norm(h, g):
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        return h * lax.rsqrt(var + model.eps) * (1.0 + g)

    def rope(t):
        half = hd // 2
        inv = 1.0 / (model.theta ** (jnp.arange(half, dtype=jnp.float32)
                                     / half))
        ang = positions[:, :, None, None].astype(jnp.float32) * inv
        c, s = jnp.cos(ang), jnp.sin(ang)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)

    h = norm(x, w["ln1"])
    q = (jnp.dot(h, w["wq"], precision=hi) + w["bq"]).reshape(B, S, nq, hd)
    k = (jnp.dot(h, w["wk"], precision=hi) + w["bk"]).reshape(B, S, nkv, hd)
    v = (jnp.dot(h, w["wv"], precision=hi) + w["bv"]).reshape(B, S, nkv, hd)
    q, k = rope(q), rope(k)
    nb = S // Q_BLOCK
    qb = q.reshape(B, nb, Q_BLOCK, nkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    pos_k = positions[0]

    def block(args):
        i, qi = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=hi) / (hd ** 0.5)
        pos_q = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = pos_k[None, :] <= pos_q[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=hi)

    o = lax.map(block, (jnp.arange(nb), qb))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, nq * hd)
    x = x + jnp.dot(o, w["wo"], precision=hi)
    h = norm(x, w["ln2"])
    a = jax.nn.silu(jnp.dot(h, w["w_gate"], precision=hi))
    return x + jnp.dot(a * jnp.dot(h, w["w_up"], precision=hi),
                       w["w_down"], precision=hi)


def _gaps(model: Qwen2, key, tokens, nxt, control: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision.HIGHEST
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    g = {n: a.astype(jnp.float32) for n, a in global_weights(model, key).items()}
    x = jnp.take(g["embed"], tokens, axis=0)
    xs = (x, x) if control else (x,)

    def body(i, xs):
        w = {n: a.astype(jnp.float32)
             for n, a in layer_weights(model, key, i).items()}
        out = [_forward_layer(model, w, xs[0], positions)]
        if control:
            wc = {n: (_fp8_round(a) if n in MATRICES else a)
                  for n, a in w.items()}
            out.append(_forward_layer(model, wc, xs[1], positions))
        return tuple(out)

    xs = lax.fori_loop(0, model.layers, body, xs)
    head = g["embed"].T if model.tied else g["lm_head"]
    head_c = _fp8_round(head) if control else None

    def final(h):
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        return h * lax.rsqrt(var + model.eps) * (1.0 + g["final_norm"])

    hs = [final(h) for h in xs]
    nr = S // ROW_BLOCK

    def rows(args):
        sl = args[0]
        t = args[1]
        lg = jnp.dot(sl, head, precision=hi)
        best = jnp.max(lg, axis=-1)
        mine = jnp.take_along_axis(lg, jnp.maximum(t, 0)[..., None],
                                   axis=-1)[..., 0]
        gap = jnp.where(t >= 0, best - mine, 0.0)
        if not control:
            return (gap,)
        lc = jnp.dot(args[2], head_c, precision=hi)
        pick = jnp.argmax(lc, axis=-1)
        theirs = jnp.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
        return gap, jnp.where(t >= 0, best - theirs, 0.0)

    def split(a):
        return a.reshape((B, nr, ROW_BLOCK) + a.shape[2:]).swapaxes(0, 1)

    args = (split(hs[0]), split(nxt)) + ((split(hs[1]),) if control else ())
    out = lax.map(rows, args)
    return tuple(o.swapaxes(0, 1).reshape(B, S) for o in out)


@functools.lru_cache(maxsize=None)
def compiled_gaps(model: Qwen2, control: bool):
    """jitted ``(key, tokens (B, S), nxt (B, S)) -> (gaps,)`` or, with
    ``control``, ``(gaps, control_gaps)``.  ``nxt[b, p]`` is the token
    served after position ``p``, or -1 where nothing is scored.  S must
    be a multiple of 256."""
    import jax
    return jax.jit(functools.partial(_gaps, model, control=control))


def bucket(n: int) -> int:
    """Padded sequence length of the reference (few shapes, few compiles)."""
    step = 512
    return max(step, -(-n // step) * step)
