"""Seeded weights, made on the device in one jitted call.

The program's parameter tree is a nested dict; each leaf is drawn from a
key folded from the seed and the leaf's path, so the draw does not depend
on the order of the leaves or on who asks: the program's init (replaced by
the harness) and the plain reference get the same numbers.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

SMALL = 0.02          # embedding, norm scales and biases
NORMS = ("ln1", "ln2", "final_norm", "qn", "kn")
BIASES = ("bq", "bk", "bv")


def base_key(seed: int) -> jax.Array:
    """The key the launcher makes from ``--seed launcher_seed(seed)``.

    The weights enter the launcher's init as that key, an argument of the
    jitted init: a key baked in as a constant would make every seed a new
    program, compiled anew in every run."""
    return jax.random.PRNGKey(launcher_seed(seed))


def launcher_seed(seed: int) -> int:
    """The launcher's ``jax.random.PRNGKey`` keeps 32 bits of a seed."""
    return int(seed) % 2 ** 32


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def fan_in(name: str, shape: tuple) -> int:
    """Contraction size of a matrix leaf (stacked leaves drop the layer)."""
    leaf = name.rsplit("/", 1)[-1]
    dims = shape[1:] if name.startswith("blocks/") else shape
    if leaf == "wo" and "/attn/" in name:
        return dims[0] * dims[1]                   # [heads, hd, d]
    return dims[0]


def std_of(name: str, shape: tuple) -> float:
    leaf = name.rsplit("/", 1)[-1]
    if leaf in NORMS or leaf in BIASES or leaf == "embed":
        return SMALL
    return 1.0 / math.sqrt(fan_in(name, shape))


def draw(key: jax.Array, name: str, shape: tuple, dtype) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    x = std_of(name, shape) * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def make(seed_key: jax.Array, abstract: dict) -> dict:
    """Tree like ``abstract`` (ShapeDtypeStruct leaves) from the key."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: draw(seed_key, path_name(p), tuple(a.shape), a.dtype),
        abstract)
