"""Weights from a seed, for every family's reference and, through the
family file, for the program: one jitted call makes every leaf on the device
in the served type."""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


class Leaf:
    """How one parameter is made: 'normal' (of ``sigma``; ``center`` takes
    each column's mean out, so that the matrix sends a constant vector to
    nought), 'ones' or 'zeros'."""

    def __init__(self, kind: str, shape, sigma: float = 0.02,
                 center: bool = False):
        self.kind, self.shape = kind, tuple(int(s) for s in shape)
        self.sigma, self.center = float(sigma), bool(center)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(spec, seed: int, dtype) -> Any:
    """Every leaf in ONE jitted call, on the device, in the served type."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Leaf))
    kinds = tuple((lf.kind, lf.shape, lf.sigma, lf.center) for lf in leaves)
    return treedef.unflatten(_make_leaves(seed_key(seed), kinds,
                                          jnp.dtype(dtype).name))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_leaves(key, kinds, dtype):
    keys = jax.random.split(key, len(kinds))
    out = []
    for k, (kind, shape, sigma, center) in zip(keys, kinds):
        if kind == "normal":
            w = sigma * jax.random.normal(k, shape, jnp.float32)
            if center:
                w = w - jnp.mean(w, axis=0, keepdims=True)
            out.append(w.astype(dtype))
        elif kind == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append(jnp.zeros(shape, dtype))
    return out


def leaf_norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


