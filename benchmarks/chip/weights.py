"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the tree and the dtypes the program serves them in.

Only the layout (names, shapes, dtypes) is taken from the program; the
values are the benchmark's own, so the plain reference can be handed the
same weights without taking anything the program made.  Each leaf draws
from its own key, folded from the seed and the leaf's index:

- a norm's ``scale`` (applied as ``1 + scale``): normal, std 0.1;
- the embedding: normal, std 0.02;
- every other leaf of two or more dimensions (a stack of ``(fan_in,
  fan_out)`` matrices, or per-head recurrent blocks): normal with std
  ``1 / sqrt(fan_in)``, ``fan_in`` its next-to-last dimension;
- any other leaf: normal, std 0.1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _std(name: str, shape) -> float:
    if name.endswith("['scale']"):
        return 0.1
    if name == "['embed']":
        return 0.02
    if len(shape) >= 2:
        return float(shape[-2]) ** -0.5
    return 0.1


def make(shapes, key):
    """``shapes``: a tree of ``jax.ShapeDtypeStruct`` (the program's
    parameter layout).  Returns the tree of weights."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    specs = [s for _, s in flat]

    @jax.jit
    def build(key):
        out = []
        for i, (name, s) in enumerate(zip(names, specs)):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, s.shape, jnp.float32) * _std(name, s.shape)
            out.append(x.astype(s.dtype))
        return out

    return jax.tree_util.tree_unflatten(tdef, build(key))
