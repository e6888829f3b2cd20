"""The dense GQA family: pre-RMSNorm blocks, rotary positions,
bidirectional grouped-query attention, a SwiGLU MLP and an untied LM
head (LLaDA-8B, ChatGLM3-6B).

``sizes`` keys: ``d_model``, ``num_heads``, ``num_kv_heads``,
``head_dim``, ``d_ff``, ``vocab_size``, ``rope`` ("standard", the whole
head, or "half", its first half), ``rope_theta``, ``norm_eps`` and
``mask_token_id``.

The reference is written from the published layer equations in
straightforward ``jax.numpy``, every matmul at ``Precision.HIGHEST``.
It imports nothing of the program under test.  Its pieces (``mm``,
``rms``, ``rope``, ``attend``, ``qkv``, ``block``, ``reference``) are
what a family of a related architecture builds on.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.families import SetupError
from bench.weights import Leaf

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# the program's kinds this family's reference computes
KINDS = {"arch_type": "dense", "attention": "gqa", "qk_norm": False,
         "act": "silu", "tie_embeddings": False}
WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
          "vocab_size")


# -- the program and the sizes ------------------------------------------------

def check_program(config: dict, kinds: dict):
    """The program's ModelConfig at the file's depth, rotary base and
    mask token; raises if its widths, rotary kind or ``kinds`` are not
    the file's."""
    from repro.configs import get_config
    s = config["sizes"]
    cfg = dataclasses.replace(get_config(config["repo_config"]),
                              num_layers=int(config["depth"]),
                              rope_theta=float(s["rope_theta"]),
                              mask_token_id=int(s["mask_token_id"]))
    got = {k: getattr(cfg, k) for k in WIDTHS + ("rope",)}
    diff = {k: (v, s[k]) for k, v in got.items() if v != s[k]}
    diff.update({k: (getattr(cfg, k), v) for k, v in kinds.items()
                 if getattr(cfg, k) != v})
    if diff:
        raise SetupError(f"{config['repo_config']} is not the configured "
                         f"model: (program, file) {diff}")
    return cfg


def program_config(config: dict):
    return check_program(config, KINDS)


def tiny_sizes(sizes: dict, tiny) -> dict:
    return dict(sizes, **{k: getattr(tiny, k) for k in WIDTHS},
                mask_token_id=tiny.mask_token_id)


def param_shapes(sizes: dict, depth: int) -> dict:
    """The served tree's leaves: 0.02 for the token embedding,
    ``1/sqrt(fan_in)`` for every projection and the LM head, ones for the
    norm scales; ``blocks/`` leaves have a leading layer axis."""
    d, v, hd = sizes["d_model"], sizes["vocab_size"], sizes["head_dim"]
    q, kv = sizes["num_heads"] * hd, sizes["num_kv_heads"] * hd
    ff = sizes["d_ff"]

    def mat(rows, cols):
        return Leaf((depth, rows, cols), rows ** -0.5)

    return {"embed/tok": Leaf((v, d), 0.02),
            "embed/head": Leaf((d, v), d ** -0.5),
            "norm_f/scale": Leaf((d,), None),
            "blocks/norm1/scale": Leaf((depth, d), None),
            "blocks/norm2/scale": Leaf((depth, d), None),
            "blocks/attn/wq": mat(d, q), "blocks/attn/wk": mat(d, kv),
            "blocks/attn/wv": mat(d, kv), "blocks/attn/wo": mat(q, d),
            "blocks/mlp/gate": mat(d, ff), "blocks/mlp/up": mat(d, ff),
            "blocks/mlp/down": mat(ff, d)}


# -- operations (a multiply-add is two) ----------------------------------------

def layer_flops(sizes: dict, rows: int, keys: int) -> int:
    """The Q/K/V/O projections, the scores and their weighted sum over
    ``keys`` keys, and the SwiGLU MLP."""
    d, hd, ff = sizes["d_model"], sizes["head_dim"], sizes["d_ff"]
    nq, nkv = sizes["num_heads"], sizes["num_kv_heads"]
    proj = 2 * rows * d * (nq + 2 * nkv) * hd + 2 * rows * nq * hd * d
    attn = 2 * 2 * rows * keys * nq * hd
    mlp = 3 * 2 * rows * d * ff
    return proj + attn + mlp


def head_flops(sizes: dict, rows: int) -> int:
    return 2 * rows * sizes["d_model"] * sizes["vocab_size"]


def keys(sizes: dict, lo: int, rows: int, total: int) -> int:
    """Bidirectional: every row attends to the whole canvas."""
    return total


def refresh_flops(sizes: dict, depth: int, lo: int, block: int,
                  total: int) -> int:
    """One full-canvas forward without the head."""
    return depth * layer_flops(sizes, total, total)


# -- the reference ------------------------------------------------------------

class Dims(NamedTuple):
    d: int
    nq: int
    nkv: int
    hd: int
    ff: int
    vocab: int
    rope: str          # "standard" (whole head) | "half" (first half)
    theta: float
    eps: float

    @classmethod
    def of(cls, sizes: dict) -> "Dims":
        return cls(sizes["d_model"], sizes["num_heads"],
                   sizes["num_kv_heads"], sizes["head_dim"], sizes["d_ff"],
                   sizes["vocab_size"], sizes["rope"],
                   float(sizes["rope_theta"]), float(sizes["norm_eps"]))


def round_fp8(a, axis):
    """``a`` rounded through float8 e4m3, one scale per slice along
    ``axis`` (the largest magnitude maps to e4m3's largest, 448)."""
    a = a.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis, keepdims=True),
                        1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, fp8=False):
    """x @ w.  The control rounds both operands through float8 as an fp8
    matmul would: a scale per row of ``x`` and per column of ``w``."""
    if fp8:
        return jnp.matmul(round_fp8(x, -1), round_fp8(w, 0), precision=HI)
    return jnp.matmul(x, w.astype(F32), precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, pos, dm: Dims):
    """Rotate (x1, x2) halves of the rotary dims by position angles."""
    rot = dm.hd if dm.rope == "standard" else dm.hd // 2
    inv = 1.0 / dm.theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def attend(q, k, v, dm: Dims):
    """Bidirectional attention; query head h reads kv group h // (nq/nkv)."""
    rep = dm.nq // dm.nkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * dm.hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)


def qkv(lp, h, pos, dm: Dims, mm_):
    """Rotated queries and keys, and values, of the normed rows ``h``."""
    b, l, _ = h.shape
    q = rope(mm_(h, lp["attn/wq"]).reshape(b, l, dm.nq, dm.hd), pos, dm)
    k = rope(mm_(h, lp["attn/wk"]).reshape(b, l, dm.nkv, dm.hd), pos, dm)
    v = mm_(h, lp["attn/wv"]).reshape(b, l, dm.nkv, dm.hd)
    return q, k, v


def block(qkv_):
    """The pre-norm layer around a projection ``qkv_``:
    ``layer(lp, x, pos, dm, fp8, ctx=None) -> (x, (k, v))``.  ``ctx =
    (k_cache, v_cache, lo)`` attends the rows of ``x`` (the window at
    ``lo``) over the cache with their own fresh K/V written in; the
    rows' own K/V are returned."""
    def layer(lp, x, pos, dm: Dims, fp8: bool, ctx=None):
        b, l, _ = x.shape
        mm_ = functools.partial(mm, fp8=fp8)
        h = rms(x, lp["norm1/scale"], dm.eps)
        q, k, v = qkv_(lp, h, pos, dm, mm_)
        kk, vv = k, v
        if ctx is not None:
            ck, cv, lo = ctx
            kk = jax.lax.dynamic_update_slice_in_dim(ck, k, lo, axis=1)
            vv = jax.lax.dynamic_update_slice_in_dim(cv, v, lo, axis=1)
        o = attend(q, kk, vv, dm).reshape(b, l, dm.nq * dm.hd)
        x = x + mm_(o, lp["attn/wo"])
        h = rms(x, lp["norm2/scale"], dm.eps)
        x = x + mm_(jax.nn.silu(mm_(h, lp["mlp/gate"]))
                    * mm_(h, lp["mlp/up"]), lp["mlp/down"])
        return x, (k, v)
    return layer


def _layers(flat):
    return {k[len("blocks/"):]: v for k, v in flat.items()
            if k.startswith("blocks/")}


def _head(flat, x, dm: Dims, fp8: bool):
    return mm(rms(x, flat["norm_f/scale"], dm.eps), flat["embed/head"], fp8)


def _embed(flat, tokens, fp8: bool):
    x = jnp.take(flat["embed/tok"], tokens, axis=0).astype(F32)
    return round_fp8(x, -1) if fp8 else x


def reference(layer):
    """(forward_rows, capture, forward_window) of the model whose layer
    is ``layer`` (as ``block`` builds it), with the embedding, the final
    norm and the head of this family."""

    @functools.partial(jax.jit, static_argnames=("dm", "rows", "fp8"))
    def rows_(flat, tokens, lo, dm: Dims, rows: int, fp8: bool):
        b, l = tokens.shape
        x = _embed(flat, tokens, fp8)
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))

        def body(x, lp):
            return layer(lp, x, pos, dm, fp8)[0], None

        x, _ = jax.lax.scan(body, x, _layers(flat))
        return _head(flat, jax.lax.dynamic_slice_in_dim(x, lo, rows, 1), dm,
                     fp8)

    @functools.partial(jax.jit, static_argnames=("dm", "fp8"))
    def capture_(flat, tokens, dm: Dims, fp8: bool):
        b, l = tokens.shape
        x = _embed(flat, tokens, fp8)
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))

        def body(x, lp):
            return layer(lp, x, pos, dm, fp8)

        _, kv = jax.lax.scan(body, x, _layers(flat))
        return kv

    @functools.partial(jax.jit, static_argnames=("dm", "fp8"))
    def window_(flat, win_tokens, lo, kv, dm: Dims, fp8: bool):
        b, w = win_tokens.shape
        x = _embed(flat, win_tokens, fp8)
        pos = lo + jnp.broadcast_to(jnp.arange(w), (b, w))

        def body(x, inp):
            lp, ck, cv = inp
            return layer(lp, x, pos, dm, fp8, (ck, cv, lo))[0], None

        x, _ = jax.lax.scan(body, x, (_layers(flat), kv[0], kv[1]))
        return _head(flat, x, dm, fp8)

    def forward_rows(flat, tokens, lo, sizes: dict, rows: int,
                     fp8: bool = False):
        """Logits (B, rows, V) of canvas rows lo..lo+rows after a full
        bidirectional pass over ``tokens`` (B, L)."""
        return rows_(flat, tokens, lo, Dims.of(sizes), rows, fp8)

    def capture(flat, tokens, sizes: dict, fp8: bool = False):
        """Every layer's K/V over the whole canvas: ((depth, B, L, G,
        hd),)*2."""
        return capture_(flat, tokens, Dims.of(sizes), fp8)

    def forward_window(flat, win_tokens, lo, kv, sizes: dict,
                       fp8: bool = False):
        """Logits (B, W, V) of the window at ``lo`` against captured
        K/V."""
        return window_(flat, win_tokens, lo, kv, Dims.of(sizes), fp8)

    return forward_rows, capture, forward_window


forward_rows, capture, forward_window = reference(block(qkv))
