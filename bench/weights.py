"""Seeded random weights of a dense GQA model, in the served dtype.

The tree is the layout the served model reads (``embed``, ``norm_f`` and
one stacked group in ``blocks``); ``bench.run`` checks it against the
program's own parameter shapes before serving.  Scales follow the usual
fan-in rule: 0.02 for the token embedding, ``1/sqrt(fan_in)`` for every
projection and the LM head, ones for the norm scales.

Every leaf comes from its own key, folded from the seed and the leaf's
name, and a stacked leaf is drawn one layer at a time inside one jitted
call, so the random bits of a single layer are the largest temporary.
The same seed always gives the same weights, whichever process asks.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# (leaf path, shape kind); a "stack" leaf has a leading layer axis
_MATRICES = (("attn/wq", "d,q"), ("attn/wk", "d,kv"), ("attn/wv", "d,kv"),
             ("attn/wo", "q,d"), ("mlp/gate", "d,ff"), ("mlp/up", "d,ff"),
             ("mlp/down", "ff,d"))


def seed_key(seed: int, tag: str) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are enough) and a
    tag naming what it seeds."""
    words = np.random.SeedSequence(
        [int(seed) & (2**64 - 1), zlib.crc32(tag.encode())]).generate_state(1)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def _dims(sizes: dict) -> dict:
    hd = sizes["head_dim"]
    return {"d": sizes["d_model"], "q": sizes["num_heads"] * hd,
            "kv": sizes["num_kv_heads"] * hd, "ff": sizes["d_ff"],
            "V": sizes["vocab_size"]}


def param_shapes(sizes: dict, depth: int) -> dict:
    """{leaf path: shape} of the tree ``make_params`` builds."""
    dims = _dims(sizes)
    shapes = {"embed/tok": (dims["V"], dims["d"]),
              "embed/head": (dims["d"], dims["V"]),
              "norm_f/scale": (dims["d"],),
              "blocks/norm1/scale": (depth, dims["d"]),
              "blocks/norm2/scale": (depth, dims["d"])}
    for path, kind in _MATRICES:
        rows, cols = kind.split(",")
        shapes["blocks/" + path] = (depth, dims[rows], dims[cols])
    return shapes


def _leaf(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _stacked(key, shape, std, dtype):
    """(depth, rows, cols): layer i from fold_in(key, i), one layer of
    random bits alive at a time."""
    def body(i, buf):
        return buf.at[i].set(_leaf(jax.random.fold_in(key, i), shape[1:],
                                   std, dtype))
    return jax.lax.fori_loop(0, shape[0], body, jnp.zeros(shape, dtype))


def make_params(sizes: dict, depth: int, seed: int, dtype=jnp.bfloat16):
    """Build the whole tree on the default device in one jitted call."""
    shapes = param_shapes(sizes, depth)
    key = seed_key(seed, "weights")

    def build(key):
        flat = {}
        for path, shape in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            if path.endswith("/scale"):
                flat[path] = jnp.ones(shape, dtype)
            elif path == "embed/tok":
                flat[path] = _leaf(k, shape, 0.02, dtype)
            elif path == "embed/head":
                flat[path] = _leaf(k, shape, shape[0] ** -0.5, dtype)
            else:
                flat[path] = _stacked(k, shape, shape[1] ** -0.5, dtype)
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
