"""Parameter spec trees.

Model code declares a nested dict of ``P`` leaf specs (shape + logical axis
names + init).  Interpreters turn the spec into real arrays, abstract
ShapeDtypeStructs (for the dry-run: no allocation), or logical-axes trees
(consumed by distributed/sharding.py to build NamedShardings).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class P:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis name per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones
    scale: Optional[float] = None     # default: 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_leaf(x) -> bool:
    return isinstance(x, P)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal_leaf(key, scale, shape, dtype):
    """One leaf drawn in float32, scaled and cast in a single program, so
    the float32 draw is its only temporary (eagerly, the scaled copy is a
    second one: ~6 GB for the stacked 7B-wide MLP leaf).  The barrier
    keeps the draw unfused from the scale, which keeps every value
    bit-identical to the eager ``normal(...) * scale``."""
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))
    return (x * scale).astype(dtype)


def init_params(spec, key, dtype):
    """Materialize real parameter arrays from a spec tree."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for p, k in zip(leaves, keys):
        if p.init == "zeros":
            out.append(jnp.zeros(p.shape, dtype))
        elif p.init == "ones":
            out.append(jnp.ones(p.shape, dtype))
        else:
            fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
            scale = p.scale if p.scale is not None else 1.0 / np.sqrt(fan_in)
            out.append(_normal_leaf(k, np.float32(scale), p.shape,
                                    jnp.dtype(dtype)))
    return jax.tree.unflatten(treedef, out)


def abstract_params(spec, dtype):
    """ShapeDtypeStruct tree — used by .lower() so nothing is allocated."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), spec, is_leaf=is_leaf)


def logical_axes(spec):
    """Tree of logical-axis tuples, same structure as the param tree."""
    return jax.tree.map(lambda p: p.axes, spec, is_leaf=is_leaf)


def count(spec) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(spec, is_leaf=is_leaf))
