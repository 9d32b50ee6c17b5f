"""The plain reference: a float32 forward pass at the highest matmul
precision, written from each published architecture.  The architecture's
module (``bench/archs/<model_type>.py``) runs its stack in
``reference_forward`` and may use the blocks below (RMSNorm, rotary
embeddings over halves, causal attention in blocks of queries); this
module scores the tokens.  It imports nothing of the program and makes
its own weights again from the seed, one layer at a time inside the
architecture's layer loop.  The head runs in blocks of positions, so a
batch of long sequences fits beside nothing else.

``gaps`` teacher-forces the served tokens: at each scored position it
returns the reference's best logit minus its logit for the token that
followed there (0 where the served token is the reference's best).
With ``control`` the architecture runs a second forward whose matrices
are rounded to float8 (e4m3, one scale per output column, ``fp8_round``)
and this module returns, beside the gaps, the reference's gap for the
token the float8 forward puts first: what a server in that precision
would have served."""

from __future__ import annotations

import functools

Q_BLOCK = 256
ROW_BLOCK = 256


def fp8_round(w):
    """Round a float32 matrix (input-major) to float8 e4m3 with one
    scale per output column, and back to float32."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rounded(w: dict, matrices) -> dict:
    """The control's copy of a layer's leaves: ``matrices`` rounded to
    float8, the rest as they are."""
    return {n: (fp8_round(a) if n in matrices else a) for n, a in w.items()}


def dot(a, b):
    import jax.numpy as jnp
    from jax import lax
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST)


def rms_norm(h, g, eps: float):
    """RMSNorm with the gain stored as ``gain - 1``."""
    import jax.numpy as jnp
    from jax import lax
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return h * lax.rsqrt(var + eps) * (1.0 + g)


def rope(t, positions, theta: float):
    """Rotary embedding over halves of the last axis of ``t`` (B, S, H,
    hd) at ``positions`` (B, S)."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)


def causal_attention(q, k, v, positions):
    """Grouped-query causal attention: q (B, S, nq, hd), k and v (B, S,
    nkv, hd); returns (B, S, nq * hd).  Queries go in blocks of
    ``Q_BLOCK``, so S must be a multiple of it."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision.HIGHEST
    B, S, nq, hd = q.shape
    nkv = k.shape[2]
    G = nq // nkv
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
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, nq * hd)


def _gaps(model, key, tokens, nxt, control: bool):
    import jax.numpy as jnp
    from jax import lax
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    hs, head = model.arch.reference_forward(model.sizes, key, tokens,
                                            positions, control)
    head_c = fp8_round(head) if control else None
    nr = S // ROW_BLOCK

    def rows(args):
        sl = args[0]
        t = args[1]
        lg = dot(sl, head)
        best = jnp.max(lg, axis=-1)
        mine = jnp.take_along_axis(lg, jnp.maximum(t, 0)[..., None],
                                   axis=-1)[..., 0]
        gap = jnp.where(t >= 0, best - mine, 0.0)
        if not control:
            return (gap,)
        lc = dot(args[2], head_c)
        pick = jnp.argmax(lc, axis=-1)
        theirs = jnp.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
        return gap, jnp.where(t >= 0, best - theirs, 0.0)

    def split(a):
        return a.reshape((B, nr, ROW_BLOCK) + a.shape[2:]).swapaxes(0, 1)

    args = (split(hs[0]), split(nxt)) + ((split(hs[1]),) if control else ())
    out = lax.map(rows, args)
    return tuple(o.swapaxes(0, 1).reshape(B, S) for o in out)


@functools.lru_cache(maxsize=None)
def compiled_gaps(model, control: bool):
    """jitted ``(key, tokens (B, S), nxt (B, S)) -> (gaps,)`` or, with
    ``control``, ``(gaps, control_gaps)`` for a ``program.Model``.
    ``nxt[b, p]`` is the token served after position ``p``, or -1 where
    nothing is scored.  S must be a multiple of 256."""
    import jax
    return jax.jit(functools.partial(_gaps, model, control=control))


def bucket(n: int) -> int:
    """Padded sequence length of the reference (few shapes, few compiles)."""
    step = 512
    return max(step, -(-n // step) * step)
