"""A toy architecture (``model_type`` "toy") for the discovery test:
periods of a dense layer and then a layer of top-k routed experts, so
the stack holds two layer shapes; no QKV bias, tied head.  The test
copies it to ``bench/archs/toy.py`` of a scratch checkout: it is added
as the next architecture would be, with no file of the harness
changed."""

from __future__ import annotations

import dataclasses

from harness import reference as ref
from harness import weights
from harness.flops import BYTES

KINDS = ("attn", "moe")  # one period: the program's unit (ATTN, MOE)
MATRICES = ("wq", "wk", "wv", "wo")
EPS = 1e-6
THETA = 10000.0


@dataclasses.dataclass(frozen=True)
class Toy:
    name: str
    hidden: int
    inter: int
    heads: int
    kv_heads: int
    layers: int
    vocab: int
    experts: int
    top_k: int

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def sizes(name: str, hf: dict) -> Toy:
    return Toy(name, hf["hidden_size"], hf["intermediate_size"],
               hf["num_attention_heads"], hf["num_key_value_heads"],
               hf["num_hidden_layers"], hf["vocab_size"],
               hf["num_experts"], hf["num_experts_per_tok"])


def model_config(m: Toy, dtype: str = "bfloat16"):
    from repro.models.config import ATTN, MOE, ModelConfig
    return ModelConfig(name=m.name, family="moe", n_layers=m.layers,
                       d_model=m.hidden, n_heads=m.heads,
                       n_kv_heads=m.kv_heads, d_ff=m.inter,
                       vocab_size=m.vocab, head_dim=m.head_dim,
                       n_experts=m.experts, top_k=m.top_k,
                       tie_embeddings=True, unit=(ATTN, MOE),
                       rope_theta=THETA, norm_eps=EPS, dtype=dtype)


def shapes(m: Toy, kind: str) -> dict:
    d, f, e = m.hidden, m.inter, m.experts
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    s = {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
         "wo": (q, d), "ln2": (d,)}
    if kind == "moe":
        s.update(router=(d, e), w_gate=(e, d, f), w_up=(e, d, f),
                 w_down=(e, f, d))
    else:
        s.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    return s


def _exponent(name: str, shape) -> int:
    if name.startswith("ln") or name == "final_norm":
        return weights.NORM_EXP
    return weights.fan_in_exponent(shape[-1] if name == "embed"
                                   else shape[-2])


def layer_weights(m: Toy, key, layer, kind: str) -> dict:
    k = weights.layer_key(key, layer)
    return {n: weights.draw(k, i, s, _exponent(n, s))
            for i, (n, s) in enumerate(shapes(m, kind).items())}


def global_weights(m: Toy, key) -> dict:
    k = weights.global_key(key)
    s = {"embed": (m.vocab, m.hidden), "final_norm": (m.hidden,)}
    return {n: weights.draw(k, i, sh, _exponent(n, sh))
            for i, (n, sh) in enumerate(s.items())}


def program_params(m: Toy, key, padded_vocab: int) -> dict:
    import jax
    import jax.numpy as jnp
    n, d, hd = m.layers // len(KINDS), m.hidden, m.head_dim
    scan = {}
    for slot, kind in enumerate(KINDS):
        stk = jax.vmap(lambda u: layer_weights(
            m, key, len(KINDS) * u + slot, kind))(jnp.arange(n))
        stk["wq"] = stk["wq"].reshape(n, d, m.heads, hd)
        stk["wk"] = stk["wk"].reshape(n, d, m.kv_heads, hd)
        stk["wv"] = stk["wv"].reshape(n, d, m.kv_heads, hd)
        stk["wo"] = stk["wo"].reshape(n, m.heads, hd, d)
        scan[f"u{slot}_{kind}"] = stk
    g = global_weights(m, key)
    return {"embed": weights.pad_vocab(g["embed"], padded_vocab, 0),
            "final_norm": g["final_norm"], "scan": scan}


def _layer(m: Toy, w: dict, x, positions):
    import jax
    import jax.numpy as jnp
    from jax import lax
    B, S, _ = x.shape
    h = ref.rms_norm(x, w["ln1"], EPS)
    q = ref.rope(ref.dot(h, w["wq"]).reshape(B, S, m.heads, -1),
                 positions, THETA)
    k = ref.rope(ref.dot(h, w["wk"]).reshape(B, S, m.kv_heads, -1),
                 positions, THETA)
    v = ref.dot(h, w["wv"]).reshape(B, S, m.kv_heads, -1)
    x = x + ref.dot(ref.causal_attention(q, k, v, positions), w["wo"])
    h = ref.rms_norm(x, w["ln2"], EPS)
    if "router" not in w:
        a = jax.nn.silu(ref.dot(h, w["w_gate"])) * ref.dot(h, w["w_up"])
        return x + ref.dot(a, w["w_down"])
    hi = lax.Precision.HIGHEST
    top, idx = lax.top_k(jax.nn.softmax(ref.dot(h, w["router"]), -1),
                         m.top_k)
    gate = jnp.sum(jax.nn.one_hot(idx, m.experts)
                   * (top / jnp.sum(top, -1, keepdims=True))[..., None], -2)
    a = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w["w_gate"],
                                precision=hi))
         * jnp.einsum("bsd,edf->bsef", h, w["w_up"], precision=hi))
    y = jnp.einsum("bsef,efd->bsed", a, w["w_down"], precision=hi)
    return x + jnp.einsum("bse,bsed->bsd", gate, y, precision=hi)


def reference_forward(m: Toy, key, tokens, positions, control: bool):
    """One period a step of the layer loop: its dense layer, then its
    experts' layer."""
    import jax.numpy as jnp
    from jax import lax

    def f32(w):
        return {n: a.astype(jnp.float32) for n, a in w.items()}

    g = f32(global_weights(m, key))
    x = jnp.take(g["embed"], tokens, axis=0)
    xs = (x, x) if control else (x,)

    def period(u, xs):
        for slot, kind in enumerate(KINDS):
            w = f32(layer_weights(m, key, len(KINDS) * u + slot, kind))
            ws = (w, ref.rounded(w, MATRICES))
            xs = tuple(_layer(m, ws[i], h, positions)
                       for i, h in enumerate(xs))
        return xs

    xs = lax.fori_loop(0, m.layers // len(KINDS), period, xs)
    return [ref.rms_norm(h, g["final_norm"], EPS) for h in xs], g["embed"].T


def _attention_params(m: Toy) -> int:
    return 2 * m.hidden * (m.heads + m.kv_heads) * m.head_dim


def matmul_params(m: Toy) -> int:
    """A token's: each period's dense layer, its attention and top-k of
    its experts, and the head."""
    ffn = 3 * m.hidden * m.inter
    period = (2 * _attention_params(m) + ffn + m.hidden * m.experts
              + m.top_k * ffn)
    return m.layers // len(KINDS) * period + m.hidden * m.vocab


def weight_bytes(m: Toy) -> int:
    ffn = 3 * m.hidden * m.inter
    period = (2 * _attention_params(m) + ffn + m.hidden * m.experts
              + m.experts * ffn)
    return BYTES * (m.layers // len(KINDS) * period + m.hidden * m.vocab)


def kv_bytes_per_token(m: Toy) -> int:
    return BYTES * 2 * m.layers * m.kv_heads * m.head_dim


def _attention_flops(m: Toy, cells: float) -> float:
    return 4.0 * m.layers * m.heads * m.head_dim * cells


def token_flops(m: Toy, context: int) -> float:
    return 2.0 * matmul_params(m) + _attention_flops(m, context + 1)


def prefill_flops(m: Toy, n: int) -> float:
    return 2.0 * matmul_params(m) * n + _attention_flops(m, n * (n + 1) / 2)


def verify_work(m: Toy, call):
    q = call.rows * (call.width + 1)
    f = 2.0 * matmul_params(m) * q + _attention_flops(
        m, (call.width + 1) * call.ctx_cells)
    b = (weight_bytes(m) + kv_bytes_per_token(m) * (call.ctx_cells + q)
         + BYTES * q * m.vocab)
    return f, b
