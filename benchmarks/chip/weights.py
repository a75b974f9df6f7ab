"""Random weights from the seed, made by the benchmark on the device in
one jitted call, in the layout and type the program serves them in.

The layout (``embed/table``, ``layers/attn/wq/w``, ...) is taken from
the program's abstract parameter tree: shapes only, no values.  The
reference reads the same arrays by those names, so the program and the
reference compute with the very weights that this module made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["base_key", "program", "make"]


def base_key(seed: int):
    """A JAX key for any non-negative seed, also one wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, jnp.uint32(word & 0xFFFFFFFF))
    return key


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _draw(key, name: str, aval):
    shape, dtype = aval.shape, aval.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("scale"):  # norm gains about 1
        x = 1.0 + 0.1 * z
    elif name.endswith("bias") or name.endswith("/b"):
        x = 0.1 * z
    elif name.endswith("table"):  # embedding rows of unit scale
        x = z
    else:  # a projection (..., d_in, d_out): unit-scale outputs
        x = z * (1.0 / math.sqrt(shape[-2]))
    return x.astype(dtype)


def program(abstract_params):
    """The function of a key that draws every leaf: one program."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    names = [_leaf_name(p) for p, _ in leaves]
    avals = [a for _, a in leaves]

    def build(key):
        keys = jax.random.split(key, len(avals))
        out = [_draw(k, n, a) for k, n, a in zip(keys, names, avals)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make(abstract_params, seed: int):
    """The whole parameter tree for ``seed``, on the default device."""
    return jax.jit(program(abstract_params))(base_key(seed))
