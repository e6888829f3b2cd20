"""The MoE block-diffusion family: pre-RMSNorm blocks, rotary positions,
grouped-query attention with per-head q/k RMSNorm, block-causal over
blocks of ``block_length`` positions, a routed SwiGLU expert layer with
no shared expert, and an untied LM head (SDAR-30B-A3B-Chat, on its
Qwen3-MoE base).

``sizes`` keys: the dense family's ``d_model``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``vocab_size``, ``rope``, ``rope_theta``,
``norm_eps`` and ``mask_token_id``; ``num_experts`` (the router's
width), ``num_experts_per_tok``, ``norm_topk_prob`` (renormalise the
top-k gates), ``moe_d_ff`` (an expert's width), ``experts_first`` and
``experts_held`` (the experts this chip holds), and ``block_length``.

The layer, as the reference computes it: ``x + Attn(RMSNorm x)``, the
queries and keys RMS-normed per head before the rotation, a position
seeing the keys of its own block and of the blocks before it; then
``x + MoE(RMSNorm x)``: the softmax router over all ``num_experts``,
the top ``num_experts_per_tok`` kept (renormalised), each a SwiGLU
expert, and of those only the held experts' part added.  Every matmul
is float32 at ``Precision.HIGHEST`` (``dense_gqa.mm``, with its float8
control); it imports nothing of the program under test.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.families import SetupError
from bench.families import dense_gqa as dense
from bench.weights import Leaf

F32 = jnp.float32
HI = dense.HI
# the reference's two mechanisms; the tests turn each off in a copy
BLOCK_CAUSAL = True          # the block-causal mask
HELD_SHARE = True            # add only the held experts' part

KINDS = {"arch_type": "moe", "attention": "gqa", "qk_norm": True,
         "act": "silu", "tie_embeddings": False, "sliding_window": 0}
WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "vocab_size",
          "rope")
MOE = {"num_experts": "num_experts",
       "num_experts_per_tok": "num_experts_per_tok",
       "moe_d_ff": "moe_d_ff", "experts_first": "held_first",
       "experts_held": "num_held"}


# -- the program and the sizes ------------------------------------------------

def program_config(config: dict):
    """The program's ModelConfig at the file's depth, rotary base, mask
    token and held experts; ``SetupError`` where the program lacks the
    model, the held-experts layer or the block-causal mask, or its
    widths or kinds are not the file's."""
    s = config["sizes"]
    try:
        from repro.configs import get_config
        base = get_config(config["repo_config"])
        cfg = dataclasses.replace(
            base, num_layers=int(config["depth"]),
            rope_theta=float(s["rope_theta"]),
            mask_token_id=int(s["mask_token_id"]),
            moe=dataclasses.replace(base.moe,
                                    held_first=int(s["experts_first"]),
                                    held_count=int(s["experts_held"])))
        got = {k: getattr(cfg, k) for k in WIDTHS}
        got.update({k: getattr(cfg.moe, v) for k, v in MOE.items()})
        got["block_length"] = cfg.attn_block
        kinds = {k: getattr(cfg, k) for k in KINDS}
        kinds["experts_shared"] = cfg.moe.num_shared_experts
        kinds["dense_layers"] = cfg.moe.first_k_dense
        # the program's router always renormalises the top-k gates
        kinds["norm_topk_prob"] = True
    except (KeyError, TypeError, AttributeError) as e:
        raise SetupError(f"the program cannot run {config['repo_config']} "
                         f"as this family needs: {type(e).__name__}: {e}")
    want = dict(KINDS, experts_shared=0, dense_layers=0,
                norm_topk_prob=s["norm_topk_prob"])
    diff = {k: (v, s[k]) for k, v in got.items() if v != s[k]}
    diff.update({k: (v, want[k]) for k, v in kinds.items() if v != want[k]})
    if diff:
        raise SetupError(f"{config['repo_config']} is not the configured "
                         f"model: (program, file) {diff}")
    return cfg


def tiny_sizes(sizes: dict, tiny) -> dict:
    """The program's ``-tiny`` preset's widths, holding the file's share
    of its experts."""
    m = tiny.moe
    held = sizes["experts_held"] * m.num_experts // sizes["num_experts"]
    return dict(sizes, **{k: getattr(tiny, k) for k in WIDTHS},
                num_experts=m.num_experts,
                num_experts_per_tok=m.num_experts_per_tok,
                moe_d_ff=m.moe_d_ff, experts_held=max(held, 1),
                experts_first=0, block_length=tiny.attn_block,
                mask_token_id=tiny.mask_token_id)


def param_shapes(sizes: dict, depth: int) -> dict:
    """The served tree's leaves: 0.02 for the token embedding,
    ``1/sqrt(fan_in)`` for every projection, the router, the experts
    and the LM head; the q/k norms' scales drawn at 1 (a served
    checkpoint's are learned, and at ones the norm would all but keep
    random projections as they are); ones for the other norms.
    ``blocks/`` leaves have a leading layer axis."""
    d, v, hd = sizes["d_model"], sizes["vocab_size"], sizes["head_dim"]
    q, kv = sizes["num_heads"] * hd, sizes["num_kv_heads"] * hd
    e, held, ff = sizes["num_experts"], sizes["experts_held"], \
        sizes["moe_d_ff"]

    def mat(*shape):
        return Leaf((depth,) + shape, shape[-2] ** -0.5)

    return {"embed/tok": Leaf((v, d), 0.02),
            "embed/head": Leaf((d, v), d ** -0.5),
            "norm_f/scale": Leaf((d,), None),
            "blocks/norm1/scale": Leaf((depth, d), None),
            "blocks/norm2/scale": Leaf((depth, d), None),
            "blocks/attn/wq": mat(d, q), "blocks/attn/wk": mat(d, kv),
            "blocks/attn/wv": mat(d, kv), "blocks/attn/wo": mat(q, d),
            "blocks/attn/q_scale": Leaf((depth, hd), 1.0),
            "blocks/attn/k_scale": Leaf((depth, hd), 1.0),
            "blocks/moe/router": mat(d, e),
            "blocks/moe/w_gate": mat(held, d, ff),
            "blocks/moe/w_up": mat(held, d, ff),
            "blocks/moe/w_down": mat(held, ff, d)}


# -- operations (a multiply-add is two) ----------------------------------------

def layer_flops(sizes: dict, rows: int, keys) -> float:
    """The Q/K/V/O projections, the scores and their weighted sum over
    ``keys`` keys, the router, and the held experts' SwiGLU at the
    expected share of the routed pairs: rows × top-k × held / experts."""
    d, hd = sizes["d_model"], sizes["head_dim"]
    nq, nkv = sizes["num_heads"], sizes["num_kv_heads"]
    e, k, ff = sizes["num_experts"], sizes["num_experts_per_tok"], \
        sizes["moe_d_ff"]
    proj = 2 * rows * d * (nq + 2 * nkv) * hd + 2 * rows * nq * hd * d
    attn = 2 * 2 * rows * keys * nq * hd
    router = 2 * rows * d * e
    experts = 3 * 2 * d * ff * rows * k * sizes["experts_held"] / e
    return proj + attn + router + experts


def head_flops(sizes: dict, rows: int) -> int:
    return 2 * rows * sizes["d_model"] * sizes["vocab_size"]


def keys(sizes: dict, lo: int, rows: int, total: int) -> float:
    """Block-causal: a row attends to the keys up to the end of its own
    block; the mean over the rows ``lo .. lo + rows``."""
    blk = sizes["block_length"]
    return sum(min(total, (i // blk + 1) * blk)
               for i in range(lo, lo + rows)) / rows


def refresh_flops(sizes: dict, depth: int, lo: int, block: int,
                  total: int) -> float:
    """What an exact block-causal cache requires at the start of the
    block at ``lo``: the K/V of the one block committed since the last
    refresh, rows ``lo - block .. lo``, through every layer (the
    prompt's own capture, before the first block, is left out: this
    term cannot tell the first block from the others)."""
    return depth * layer_flops(sizes, block,
                               keys(sizes, lo - block, block, total))


# -- the reference ------------------------------------------------------------

class Dims(NamedTuple):
    d: int
    nq: int
    nkv: int
    hd: int
    vocab: int
    rope: str          # "standard" (whole head) | "half" (first half)
    theta: float
    eps: float
    experts: int
    topk: int
    norm_topk: bool
    first: int
    held: int
    block: int

    @classmethod
    def of(cls, sizes: dict) -> "Dims":
        return cls(sizes["d_model"], sizes["num_heads"],
                   sizes["num_kv_heads"], sizes["head_dim"],
                   sizes["vocab_size"], sizes["rope"],
                   float(sizes["rope_theta"]), float(sizes["norm_eps"]),
                   sizes["num_experts"], sizes["num_experts_per_tok"],
                   bool(sizes["norm_topk_prob"]), sizes["experts_first"],
                   sizes["experts_held"], sizes["block_length"])


def attend(q, k, v, qpos, kpos, dm: Dims):
    """Query head h reads kv group h // (nq/nkv); position i sees key j
    iff j // block <= i // block."""
    rep = dm.nq // dm.nkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * dm.hd ** -0.5
    if BLOCK_CAUSAL:
        seen = kpos[None, :] // dm.block <= qpos[:, None] // dm.block
        s = jnp.where(seen, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)


def experts(lp, h, dm: Dims, fp8: bool):
    """The held experts' part of the expert layer over the rows ``h``:
    every held expert runs over every row, weighted by the row's gate
    for it (zero where the router did not pick it)."""
    logits = dense.mm(h, lp["moe/router"], fp8)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), dm.topk)
    if dm.norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    local = ids - dm.first                   # out of range: held elsewhere
    if not HELD_SHARE:
        # the broken copy: every routed expert's part added, the absent
        # ones read from the held experts' weights
        local = local % dm.held
    g = jnp.sum(jax.nn.one_hot(local, dm.held, dtype=F32)
                * gates[..., None], axis=-2)                 # (B, L, held)

    def one(wg, wu, wd):
        return dense.mm(jax.nn.silu(dense.mm(h, wg, fp8))
                        * dense.mm(h, wu, fp8), wd, fp8)

    ys = jax.vmap(one)(lp["moe/w_gate"], lp["moe/w_up"], lp["moe/w_down"])
    return jnp.einsum("ble,ebld->bld", g, ys, precision=HI)


def layer(lp, x, pos, dm: Dims, fp8: bool, ctx=None):
    """One layer over the rows ``x`` at positions ``pos`` (B, L).  ``ctx
    = (k_cache, v_cache, lo)`` attends the rows (the window at ``lo``)
    over the cache with their own fresh K/V written in.  Returns the new
    rows and their own K/V."""
    b, l, _ = x.shape
    mm_ = functools.partial(dense.mm, fp8=fp8)
    h = dense.rms(x, lp["norm1/scale"], dm.eps)
    q = mm_(h, lp["attn/wq"]).reshape(b, l, dm.nq, dm.hd)
    k = mm_(h, lp["attn/wk"]).reshape(b, l, dm.nkv, dm.hd)
    v = mm_(h, lp["attn/wv"]).reshape(b, l, dm.nkv, dm.hd)
    q = dense.rope(dense.rms(q, lp["attn/q_scale"], dm.eps), pos, dm)
    k = dense.rope(dense.rms(k, lp["attn/k_scale"], dm.eps), pos, dm)
    kk, vv = k, v
    if ctx is not None:
        ck, cv, lo = ctx
        kk = jax.lax.dynamic_update_slice_in_dim(ck, k, lo, axis=1)
        vv = jax.lax.dynamic_update_slice_in_dim(cv, v, lo, axis=1)
    o = attend(q, kk, vv, pos[0], jnp.arange(kk.shape[1]), dm)
    x = x + mm_(o.reshape(b, l, dm.nq * dm.hd), lp["attn/wo"])
    h = dense.rms(x, lp["norm2/scale"], dm.eps)
    return x + experts(lp, h, dm, fp8), (k, v)


def _layers(flat):
    return {k[len("blocks/"):]: v for k, v in flat.items()
            if k.startswith("blocks/")}


@functools.partial(jax.jit, static_argnames=("dm", "rows", "fp8"))
def _rows(flat, tokens, lo, dm: Dims, rows: int, fp8: bool):
    b, l = tokens.shape
    x = dense._embed(flat, tokens, fp8)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def body(x, lp):
        return layer(lp, x, pos, dm, fp8)[0], None

    x, _ = jax.lax.scan(body, x, _layers(flat))
    return dense._head(flat, jax.lax.dynamic_slice_in_dim(x, lo, rows, 1),
                       dm, fp8)


@functools.partial(jax.jit, static_argnames=("dm", "fp8"))
def _capture(flat, tokens, dm: Dims, fp8: bool):
    b, l = tokens.shape
    x = dense._embed(flat, tokens, fp8)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def body(x, lp):
        return layer(lp, x, pos, dm, fp8)

    _, kv = jax.lax.scan(body, x, _layers(flat))
    return kv


@functools.partial(jax.jit, static_argnames=("dm", "fp8"))
def _window(flat, win_tokens, lo, kv, dm: Dims, fp8: bool):
    b, w = win_tokens.shape
    x = dense._embed(flat, win_tokens, fp8)
    pos = lo + jnp.broadcast_to(jnp.arange(w), (b, w))

    def body(x, inp):
        lp, ck, cv = inp
        return layer(lp, x, pos, dm, fp8, (ck, cv, lo))[0], None

    x, _ = jax.lax.scan(body, x, (_layers(flat), kv[0], kv[1]))
    return dense._head(flat, x, dm, fp8)


def forward_rows(flat, tokens, lo, sizes: dict, rows: int,
                 fp8: bool = False):
    """Logits (B, rows, V) of canvas rows lo..lo+rows after a full pass
    over ``tokens`` (B, L), a layer at a time."""
    return _rows(flat, tokens, lo, Dims.of(sizes), rows, fp8)


def capture(flat, tokens, sizes: dict, fp8: bool = False):
    """Every layer's K/V over the whole canvas: ((depth, B, L, G,
    hd),)*2."""
    return _capture(flat, tokens, Dims.of(sizes), fp8)


def forward_window(flat, win_tokens, lo, kv, sizes: dict,
                   fp8: bool = False):
    """Logits (B, W, V) of the window at ``lo`` against captured K/V."""
    return _window(flat, win_tokens, lo, kv, Dims.of(sizes), fp8)
