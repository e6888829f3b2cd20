"""Seeded random weights, in the served dtype, of any family's tree.

The tree is the layout the served model reads (``embed``, ``norm_f`` and
one stacked group in ``blocks``); the family (``bench/families/``) gives
each leaf's path, shape and scale, and ``bench.run`` checks the tree
against the program's own parameter shapes before serving.

Every leaf comes from its own key, folded from the seed and the leaf's
path, and a stacked leaf (``blocks/...``, a leading layer axis) is drawn
one layer at a time inside one jitted call, so the random bits of a
single layer are the largest temporary.  The same seed always gives the
same weights, whichever process asks.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Leaf(NamedTuple):
    """A leaf's shape and how it is drawn: normal with standard deviation
    ``std``, or ones where ``std`` is None."""
    shape: tuple
    std: Optional[float]


def seed_key(seed: int, tag: str) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are enough) and a
    tag naming what it seeds."""
    words = np.random.SeedSequence(
        [int(seed) & (2**64 - 1), zlib.crc32(tag.encode())]).generate_state(1)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def _leaf(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _stacked(key, shape, std, dtype):
    """(depth, ...): layer i from fold_in(key, i), one layer of random
    bits alive at a time."""
    def body(i, buf):
        return buf.at[i].set(_leaf(jax.random.fold_in(key, i), shape[1:],
                                   std, dtype))
    return jax.lax.fori_loop(0, shape[0], body, jnp.zeros(shape, dtype))


def make_params(shapes: dict, seed: int, dtype=jnp.bfloat16):
    """Build the tree of ``shapes`` ({path: Leaf}, a family's
    ``param_shapes``) on the default device in one jitted call."""
    key = seed_key(seed, "weights")

    def build(key):
        flat = {}
        for path, leaf in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            if leaf.std is None:
                flat[path] = jnp.ones(leaf.shape, dtype)
            elif path.startswith("blocks/"):
                flat[path] = _stacked(k, leaf.shape, leaf.std, dtype)
            else:
                flat[path] = _leaf(k, leaf.shape, leaf.std, dtype)
        return flat

    flat = jax.jit(build)(key)
    return unflatten(flat)


def unflatten(flat: dict) -> dict:
    """{"a/b/c": x} -> the served tree; ``blocks`` is a one-group list."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    tree["blocks"] = [tree["blocks"]]
    return tree


def flatten(tree: dict) -> dict:
    """Inverse of ``unflatten``."""
    out = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}/{k}" if prefix else k, v)
        else:
            out[prefix] = node

    walk("", {**tree, "blocks": tree["blocks"][0]})
    return out
