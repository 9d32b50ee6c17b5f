"""Seeded random weights for a Qwen2 configuration, made on the device.

Every weight is a small integer times a power of two, stored in
bfloat16: ``(b - 128) * 2**-e`` with ``b`` a random byte.  Such values
are exact in bfloat16 and in float32, so the program's copy and the
reference's copy (made again from the same seed, layer by layer) agree
bit for bit whatever order XLA computes them in.  Matrices get the scale
of 1/sqrt(fan_in); norm gains are ``1 + (b - 128) * 2**-9`` and biases
``(b - 128) * 2**-10``.

Layout: the canonical leaves below follow the published Qwen2 modules
(``q_proj`` ... ``down_proj``, stored input-major).  ``program_params``
rearranges them into the tree the program's ``models/transformer.py``
serves, whose norm weights are stored as ``gain - 1``."""

from __future__ import annotations

import dataclasses
import math

import numpy as np

BYTE_STD = float(np.std(np.arange(256) - 128.0))
NORM_EXP = 9
BIAS_EXP = 10
LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")


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


def _exponent(name: str, shape) -> int:
    if name in ("ln1", "ln2", "final_norm"):
        return NORM_EXP
    if name in ("bq", "bk", "bv"):
        return BIAS_EXP
    fan_in = shape[-1] if name == "embed" else shape[0]
    return int(round(math.log2(BYTE_STD * math.sqrt(fan_in))))


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    import jax
    s = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF),
                              (s >> 32) & 0xFFFFFFFF)


def _leaf(key, name: str, shape, index: int):
    import jax
    import jax.numpy as jnp
    b = jax.random.bits(jax.random.fold_in(key, index), shape, jnp.uint8)
    v = b.astype(jnp.bfloat16) - jnp.bfloat16(128)
    return v * jnp.bfloat16(2.0 ** -_exponent(name, shape))


def layer_weights(model: Qwen2, model_key, layer: int) -> dict:
    """Canonical bf16 leaves of one layer (norms as ``gain - 1``)."""
    import jax
    key = jax.random.fold_in(model_key, layer + 1)
    return {n: _leaf(key, n, s, LAYER_LEAVES.index(n))
            for n, s in model.layer_shapes().items()}


def global_weights(model: Qwen2, model_key) -> dict:
    import jax
    key = jax.random.fold_in(model_key, 0)
    return {n: _leaf(key, n, s, GLOBAL_LEAVES.index(n))
            for n, s in model.global_shapes().items()}


def model_key(seed: int, index: int):
    """Key of model ``index`` of a zoo (0 = target, 1.. = drafters)."""
    import jax
    return jax.random.fold_in(seed_key(seed), index)


def program_params(model: Qwen2, model_key, padded_vocab: int) -> dict:
    """The program's parameter tree for ``model``: layers stacked on a
    leading axis for its scan, heads split out of the projections, the
    vocabulary padded with zero rows to ``padded_vocab``.  Trace it
    under ``jax.jit`` so the whole tree is made on the device in one
    call."""
    import jax
    import jax.numpy as jnp
    d, hd = model.hidden, model.head_dim
    nq, nkv = model.heads, model.kv_heads
    # one vmapped draw per leaf writes the stacked leaf directly; each
    # slice equals ``layer_weights`` of that layer
    stk = jax.vmap(lambda i: layer_weights(model, model_key, i))(
        jnp.arange(model.layers))
    L = model.layers
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
    g = global_weights(model, model_key)
    pad = padded_vocab - model.vocab
    out = {"embed": jnp.pad(g["embed"], ((0, pad), (0, 0))),
           "final_norm": g["final_norm"], "scan": {"u0_attn": unit}}
    if not model.tied:
        out["lm_head"] = jnp.pad(g["lm_head"], ((0, 0), (0, pad)))
    return out
