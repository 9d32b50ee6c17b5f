"""Seeded random weights, made on the device: the draw every architecture
module (``bench/archs/``) makes its leaves with.

Every weight is a small integer times a power of two, stored in
bfloat16: ``(b - 128) * 2**-e`` with ``b`` a random byte.  Such values
are exact in bfloat16 and in float32, so the program's copy and the
reference's copy (made again from the same seed, layer by layer) agree
bit for bit whatever order XLA computes them in.  Matrices get the scale
of 1/sqrt(fan_in) (``fan_in_exponent``); norm gains are
``1 + (b - 128) * 2**-NORM_EXP`` (stored as ``gain - 1``) and biases
``(b - 128) * 2**-BIAS_EXP``.

Keys: model ``index`` of a zoo draws from ``model_key(seed, index)``;
its global leaves (embedding, final norm, head) from ``global_key`` and
layer ``i``'s leaves from ``layer_key(.., i)``; each leaf from its own
index under that key."""

from __future__ import annotations

import math

import numpy as np

BYTE_STD = float(np.std(np.arange(256) - 128.0))
NORM_EXP = 9
BIAS_EXP = 10


def fan_in_exponent(fan_in: int) -> int:
    """The ``e`` that gives a matrix of this fan-in a scale of about
    1/sqrt(fan_in)."""
    return int(round(math.log2(BYTE_STD * math.sqrt(fan_in))))


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    import jax
    s = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF),
                              (s >> 32) & 0xFFFFFFFF)


def model_key(seed: int, index: int):
    """Key of model ``index`` of a zoo (0 = target, 1.. = drafters)."""
    import jax
    return jax.random.fold_in(seed_key(seed), index)


def global_key(model_key):
    import jax
    return jax.random.fold_in(model_key, 0)


def layer_key(model_key, layer):
    import jax
    return jax.random.fold_in(model_key, layer + 1)


def draw(key, index: int, shape, exponent: int):
    """Leaf ``index`` under ``key``: bf16 ``(b - 128) * 2**-exponent``."""
    import jax
    import jax.numpy as jnp
    b = jax.random.bits(jax.random.fold_in(key, index), shape, jnp.uint8)
    v = b.astype(jnp.bfloat16) - jnp.bfloat16(128)
    return v * jnp.bfloat16(2.0 ** -exponent)


def pad_vocab(a, padded_vocab: int, axis: int):
    """``a`` with zero rows (``axis`` 0) or columns (1) up to the
    program's padded vocabulary."""
    import jax.numpy as jnp
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, padded_vocab - a.shape[axis])
    return jnp.pad(a, pad)
