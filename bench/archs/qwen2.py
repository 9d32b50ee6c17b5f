"""Qwen2 (``model_type`` "qwen2"): RMSNorm, rotary embeddings over
halves, grouped-query attention with QKV bias, SwiGLU MLP, tied or
untied head; every layer of one shape.

The architecture's whole surface to the harness (``harness/spec.py``'s
``load_arch``): its sizes from a configuration, the program's
``ModelConfig``, the seeded weights and the program's tree of them, the
float32 reference forward, and the work a token, a prompt and a verify
pass take.

Weights (``harness.weights``): the canonical leaves follow the published
Qwen2 modules (``q_proj`` ... ``down_proj``, stored input-major).
``program_params`` rearranges them into the tree the program's
``models/transformer.py`` serves, whose norm weights are stored as
``gain - 1``."""

from __future__ import annotations

import dataclasses
import math

from harness import reference as ref
from harness import weights
from harness.flops import BYTES

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")
NORMS = ("ln1", "ln2", "final_norm")
BIASES = ("bq", "bk", "bv")
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Qwen2:
    """The published sizes of one Qwen2 model, read from its config."""
    name: str
    hidden: int
    inter: int
    heads: int
    kv_heads: int
    layers: int
    vocab: int
    eps: float
    theta: float
    tied: bool

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @classmethod
    def from_hf(cls, name: str, hf: dict) -> "Qwen2":
        if hf.get("hidden_act", "silu") != "silu":
            raise ValueError(f"{name}: only SiLU MLPs are supported")
        return cls(name=name, hidden=hf["hidden_size"],
                   inter=hf["intermediate_size"],
                   heads=hf["num_attention_heads"],
                   kv_heads=hf["num_key_value_heads"],
                   layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
                   eps=hf["rms_norm_eps"], theta=hf["rope_theta"],
                   tied=hf["tie_word_embeddings"])

    def layer_shapes(self) -> dict:
        d, f = self.hidden, self.inter
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {"ln1": (d,), "wq": (d, q), "bq": (q,), "wk": (d, kv),
                "bk": (kv,), "wv": (d, kv), "bv": (kv,), "wo": (q, d),
                "ln2": (d,), "w_gate": (d, f), "w_up": (d, f),
                "w_down": (f, d)}

    def global_shapes(self) -> dict:
        out = {"embed": (self.vocab, self.hidden),
               "final_norm": (self.hidden,)}
        if not self.tied:
            out["lm_head"] = (self.hidden, self.vocab)
        return out

    def params_count(self) -> int:
        """Parameters of the model as published (untied head included)."""
        n = sum(math.prod(s) for s in self.layer_shapes().values())
        g = sum(math.prod(s) for s in self.global_shapes().values())
        return self.layers * n + g


def sizes(name: str, hf: dict) -> Qwen2:
    return Qwen2.from_hf(name, hf)


def model_config(m: Qwen2, dtype: str = "bfloat16"):
    from repro.models.config import ATTN, ModelConfig
    return ModelConfig(name=m.name, family="dense", n_layers=m.layers,
                       d_model=m.hidden, n_heads=m.heads,
                       n_kv_heads=m.kv_heads, d_ff=m.inter,
                       vocab_size=m.vocab, head_dim=m.head_dim,
                       qkv_bias=True, tie_embeddings=m.tied, unit=(ATTN,),
                       rope_theta=m.theta, norm_eps=m.eps, dtype=dtype)


# ----------------------------------------------------------------- weights --

def _exponent(name: str, shape) -> int:
    if name in NORMS:
        return weights.NORM_EXP
    if name in BIASES:
        return weights.BIAS_EXP
    return weights.fan_in_exponent(shape[-1] if name == "embed" else shape[0])


def layer_weights(m: Qwen2, model_key, layer: int) -> dict:
    """Canonical bf16 leaves of one layer (norms as ``gain - 1``)."""
    key = weights.layer_key(model_key, layer)
    return {n: weights.draw(key, LAYER_LEAVES.index(n), s, _exponent(n, s))
            for n, s in m.layer_shapes().items()}


def global_weights(m: Qwen2, model_key) -> dict:
    key = weights.global_key(model_key)
    return {n: weights.draw(key, GLOBAL_LEAVES.index(n), s, _exponent(n, s))
            for n, s in m.global_shapes().items()}


def program_params(m: Qwen2, model_key, padded_vocab: int) -> dict:
    """The program's parameter tree: layers stacked on a leading axis
    for its scan, heads split out of the projections, the vocabulary
    padded with zero rows to ``padded_vocab``.  Trace it under
    ``jax.jit`` so the whole tree is made on the device in one call."""
    import jax
    import jax.numpy as jnp
    d, hd = m.hidden, m.head_dim
    nq, nkv = m.heads, m.kv_heads
    # one vmapped draw per leaf writes the stacked leaf directly; each
    # slice equals ``layer_weights`` of that layer
    stk = jax.vmap(lambda i: layer_weights(m, model_key, i))(
        jnp.arange(m.layers))
    L = m.layers
    unit = {
        "ln1": stk["ln1"], "ln2": stk["ln2"],
        "wq": stk["wq"].reshape(L, d, nq, hd),
        "wk": stk["wk"].reshape(L, d, nkv, hd),
        "wv": stk["wv"].reshape(L, d, nkv, hd),
        "wo": stk["wo"].reshape(L, nq, hd, d),
        "bq": stk["bq"].reshape(L, nq, hd),
        "bk": stk["bk"].reshape(L, nkv, hd),
        "bv": stk["bv"].reshape(L, nkv, hd),
        "w_gate": stk["w_gate"], "w_up": stk["w_up"],
        "w_down": stk["w_down"],
    }
    g = global_weights(m, model_key)
    out = {"embed": weights.pad_vocab(g["embed"], padded_vocab, 0),
           "final_norm": g["final_norm"], "scan": {"u0_attn": unit}}
    if not m.tied:
        out["lm_head"] = weights.pad_vocab(g["lm_head"], padded_vocab, 1)
    return out


# --------------------------------------------------------------- reference --

def _layer(m: Qwen2, w: dict, x, positions):
    import jax
    B, S, _ = x.shape
    nq, nkv, hd = m.heads, m.kv_heads, m.head_dim
    h = ref.rms_norm(x, w["ln1"], m.eps)
    q = (ref.dot(h, w["wq"]) + w["bq"]).reshape(B, S, nq, hd)
    k = (ref.dot(h, w["wk"]) + w["bk"]).reshape(B, S, nkv, hd)
    v = (ref.dot(h, w["wv"]) + w["bv"]).reshape(B, S, nkv, hd)
    q, k = ref.rope(q, positions, m.theta), ref.rope(k, positions, m.theta)
    x = x + ref.dot(ref.causal_attention(q, k, v, positions), w["wo"])
    h = ref.rms_norm(x, w["ln2"], m.eps)
    a = jax.nn.silu(ref.dot(h, w["w_gate"]))
    return x + ref.dot(a * ref.dot(h, w["w_up"]), w["w_down"])


def reference_forward(m: Qwen2, key, tokens, positions, control: bool):
    """The stack in float32 over ``tokens`` (B, S): returns the hidden
    states after the final norm (a second, of the float8 control's
    forward, with ``control``) and the head, (hidden, vocab).  One
    layer's weights are made at a time, inside the layer loop."""
    import jax.numpy as jnp
    from jax import lax
    g = {n: a.astype(jnp.float32)
         for n, a in global_weights(m, key).items()}
    x = jnp.take(g["embed"], tokens, axis=0)
    xs = (x, x) if control else (x,)

    def body(i, xs):
        w = {n: a.astype(jnp.float32)
             for n, a in layer_weights(m, key, i).items()}
        out = [_layer(m, w, xs[0], positions)]
        if control:
            out.append(_layer(m, ref.rounded(w, MATRICES), xs[1], positions))
        return tuple(out)

    xs = lax.fori_loop(0, m.layers, body, xs)
    head = g["embed"].T if m.tied else g["lm_head"]
    return [ref.rms_norm(h, g["final_norm"], m.eps) for h in xs], head


# -------------------------------------------------------------------- work --

def layer_params(m: Qwen2) -> int:
    return sum(math.prod(s) for s in m.layer_shapes().values())


def matmul_params(m: Qwen2) -> int:
    """Parameters a token multiplies through: every layer and the head
    (the embedding lookup is a gather, not a matmul)."""
    return m.layers * layer_params(m) + m.hidden * m.vocab


def weight_bytes(m: Qwen2) -> int:
    """Bytes a forward pass reads of the weights: every layer, the
    head, and the final norm."""
    return BYTES * (matmul_params(m) + m.hidden)


def kv_bytes_per_token(m: Qwen2) -> int:
    return BYTES * 2 * m.layers * m.kv_heads * m.head_dim


def attention_flops(m: Qwen2, queries: int, context: int) -> float:
    """QK^T and PV of ``queries`` tokens over ``context`` cells each."""
    return 4.0 * m.layers * m.heads * m.head_dim * queries * context


def token_flops(m: Qwen2, context: int) -> float:
    """One token through the model at position ``context``."""
    return 2.0 * matmul_params(m) + attention_flops(m, 1, context + 1)


def prefill_flops(m: Qwen2, n: int) -> float:
    """A prompt of n tokens, causal attention."""
    return 2.0 * matmul_params(m) * n + attention_flops(m, 1, n * (n + 1) / 2)


def verify_work(m: Qwen2, call):
    """(FLOPs, bytes) of the packed verify pass of a step ``call``
    (``session.Call``): ``rows`` verified rows of ``width`` + 1 query
    tokens each over ``ctx_cells`` cached cells in all: the weights read
    once, the rows' KV read once, the logits written once.  A dense
    model's work depends on nothing else the step reported."""
    rows, width, ctx_cells = call.rows, call.width, call.ctx_cells
    q = rows * (width + 1)
    f = (2.0 * matmul_params(m) * q
         + attention_flops(m, width + 1, ctx_cells)
         + attention_flops(m, rows, (width + 1) * (width + 2) / 2))
    b = (weight_bytes(m) + kv_bytes_per_token(m) * (ctx_cells + q)
         + BYTES * q * m.vocab)
    return f, b
