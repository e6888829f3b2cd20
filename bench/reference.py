"""The plain reference: a dense GQA masked-diffusion LM in float32.

Written from the published layer equations (pre-RMSNorm blocks, rotary
positions, bidirectional grouped-query attention, SwiGLU MLP, untied LM
head) in straightforward ``jax.numpy``, every matmul at
``Precision.HIGHEST``.  It imports nothing of the program under test; it
reads the weights that ``bench.weights`` draws from the seed.

``replay`` checks served tokens.  A served token was committed as the
argmax of the model's logits at some step of its block, on the canvas
that held the tokens committed before it.  The order of commits is not
served, and on random weights the masked positions' confidences lie
within the program's bf16 rounding of each other, so the reference
cannot rebuild the program's order from its own confidences.  The
replay goes block by block, step by step with the program's commit
widths: it scores the canvas with the reference and commits the open
positions whose served token lies least below the reference's best
(the most confident first among equals), writing in the served tokens.
Each served token is read at the step, of those its position was open
in, where it lay least below the reference's best: the canvas closest
to the one it was committed on.  Its gap there is the reference's best
logit minus its own; the check compares the share of tokens with a gap
and reports the widest.  For
``cache_policy = dual`` the reference computes the same approximation:
the K/V of the whole canvas captured at each block's start, the block's
own rows recomputed at every step against them.

``control=True`` also runs the model with both operands of every
projection and of the head rounded through float8 e4m3 (a scale per
row of the activations and per column of the weights, as an fp8 matmul
would) and reads, at each served token's step, the gap of the token
that the float8 model puts first.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

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


def _fp8(a, axis):
    """``a`` rounded through float8 e4m3, one scale per slice along
    ``axis`` (the largest magnitude maps to e4m3's largest, 448)."""
    a = a.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis, keepdims=True),
                        1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, fp8=False):
    """x @ w.  The control rounds both operands through float8 as an fp8
    matmul would: a scale per row of ``x`` and per column of ``w``."""
    if fp8:
        return jnp.matmul(_fp8(x, -1), _fp8(w, 0), precision=HI)
    return jnp.matmul(x, w.astype(F32), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, dm: Dims):
    """Rotate (x1, x2) halves of the rotary dims by position angles."""
    rot = dm.hd if dm.rope == "standard" else dm.hd // 2
    inv = 1.0 / dm.theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _attend(q, k, v, dm: Dims):
    """Bidirectional attention; query head h reads kv group h // (nq/nkv)."""
    rep = dm.nq // dm.nkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * dm.hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)


def _layer(lp, x, pos, dm: Dims, fp8: bool, ctx=None):
    """One block. ``ctx = (k_cache, v_cache, lo)`` attends the rows of
    ``x`` (the window at ``lo``) over the cache with their own fresh K/V
    written in. Returns (x, (k, v)) with the rows' own K/V."""
    b, l, _ = x.shape
    mm = functools.partial(_mm, fp8=fp8)
    h = _rms(x, lp["norm1/scale"], dm.eps)
    q = _rope(mm(h, lp["attn/wq"]).reshape(b, l, dm.nq, dm.hd), pos, dm)
    k = _rope(mm(h, lp["attn/wk"]).reshape(b, l, dm.nkv, dm.hd), pos, dm)
    v = mm(h, lp["attn/wv"]).reshape(b, l, dm.nkv, dm.hd)
    kk, vv = k, v
    if ctx is not None:
        ck, cv, lo = ctx
        kk = jax.lax.dynamic_update_slice_in_dim(ck, k, lo, axis=1)
        vv = jax.lax.dynamic_update_slice_in_dim(cv, v, lo, axis=1)
    o = _attend(q, kk, vv, dm).reshape(b, l, dm.nq * dm.hd)
    x = x + mm(o, lp["attn/wo"])
    h = _rms(x, lp["norm2/scale"], dm.eps)
    x = x + mm(jax.nn.silu(mm(h, lp["mlp/gate"])) * mm(h, lp["mlp/up"]),
               lp["mlp/down"])
    return x, (k, v)


def _layers(flat):
    return {k[len("blocks/"):]: v for k, v in flat.items()
            if k.startswith("blocks/")}


def _head(flat, x, dm: Dims, fp8: bool):
    return _mm(_rms(x, flat["norm_f/scale"], dm.eps), flat["embed/head"],
               fp8)


def _embed(flat, tokens, fp8: bool):
    x = jnp.take(flat["embed/tok"], tokens, axis=0).astype(F32)
    return _fp8(x, -1) if fp8 else x


@functools.partial(jax.jit, static_argnames=("dm", "rows", "fp8"))
def forward_rows(flat, tokens, lo, dm: Dims, rows: int, fp8: bool = False):
    """Logits (B, rows, V) of canvas rows lo..lo+rows after a full
    bidirectional pass over ``tokens`` (B, L)."""
    b, l = tokens.shape
    x = _embed(flat, tokens, fp8)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def body(x, lp):
        return _layer(lp, x, pos, dm, fp8)[0], None

    x, _ = jax.lax.scan(body, x, _layers(flat))
    return _head(flat, jax.lax.dynamic_slice_in_dim(x, lo, rows, 1), dm,
                 fp8)


@functools.partial(jax.jit, static_argnames=("dm", "fp8"))
def capture(flat, tokens, dm: Dims, fp8: bool = False):
    """Every layer's K/V over the whole canvas: ((depth, B, L, G, hd),)*2."""
    b, l = tokens.shape
    x = _embed(flat, tokens, fp8)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def body(x, lp):
        return _layer(lp, x, pos, dm, fp8)

    _, kv = jax.lax.scan(body, x, _layers(flat))
    return kv


@functools.partial(jax.jit, static_argnames=("dm", "fp8"))
def forward_window(flat, win_tokens, lo, kv, dm: Dims, fp8: bool = False):
    """Logits (B, W, V) of the window at ``lo`` against captured K/V."""
    b, w = win_tokens.shape
    x = _embed(flat, win_tokens, fp8)
    pos = lo + jnp.broadcast_to(jnp.arange(w), (b, w))

    def body(x, inp):
        lp, ck, cv = inp
        return _layer(lp, x, pos, dm, fp8, (ck, cv, lo))[0], None

    x, _ = jax.lax.scan(body, x, (_layers(flat), kv[0], kv[1]))
    return _head(flat, x, dm, fp8)


@jax.jit
def _read(logits, served, ctrl_logits):
    """Per row: the served token's gap below the best logit, the
    reference's confidence (softmax max), and the control's top token's
    gap (0 without a control)."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    conf = 1.0 / jnp.sum(jnp.exp(logits - best[..., None]), -1)
    if ctrl_logits is None:
        cgap = jnp.zeros_like(best)
    else:
        top = jnp.argmax(ctrl_logits, -1)
        cgap = best - jnp.take_along_axis(logits, top[..., None], -1)[..., 0]
    return best - got, conf, cgap


def commit_widths(gen: int, block: int, steps: int) -> list:
    """Per block, the tokens committed at each step: ``steps`` spread
    over the blocks and each block's width over its steps, remainders to
    the front."""
    nb = gen // block
    base, rem = divmod(steps, nb)
    out = []
    for b in range(nb):
        spb = base + (1 if b < rem else 0)
        w, wr = divmod(block, spb)
        out.append([w + 1] * wr + [w] * (spb - wr))
    return out


def pick(conf, gap, cand, n: int) -> np.ndarray:
    """The ``n`` positions of ``cand`` that one replayed step commits:
    the least gap first, the most confident first among equal gaps."""
    return cand[np.lexsort((-conf[cand], gap[cand]))[:n]]


def replay(flat, sizes: dict, prompts: np.ndarray, served: np.ndarray,
           geometry: dict, mask_id: int, control: bool = False):
    """Replay requests of one prompt length. prompts (R, Lp) and served
    (R, gen) int arrays. Returns (gaps (R, gen), ctrl_gaps (R, gen)):
    each served token's least gap over the steps its position was open,
    and the control's top token's gap at that step (0 without a
    control); NaN where the served token is the mask id (a greedy step
    that picks the mask token as its argmax leaves the position masked,
    so nothing was served there)."""
    dm = Dims.of(sizes)
    r, lp = prompts.shape
    gen, bs = geometry["gen_length"], geometry["block_size"]
    dual = geometry["cache_policy"] == "dual"
    canvas = np.concatenate(
        [prompts, np.full((r, gen), mask_id, prompts.dtype)], 1)
    gaps = np.full((r, gen), np.inf)
    cgaps = np.full((r, gen), np.nan)
    for blk, widths in enumerate(commit_widths(gen, bs, geometry["steps"])):
        lo = lp + blk * bs
        cols = slice(blk * bs, (blk + 1) * bs)
        block_served = served[:, cols]
        if dual:
            kv = capture(flat, jnp.asarray(canvas), dm)
            ckv = capture(flat, jnp.asarray(canvas), dm, True) if control \
                else None
        # a served mask id was never committed: the position stayed
        # masked (the argmax was the mask token itself)
        open_ = block_served != mask_id
        for n in widths:
            if dual:
                win = jnp.asarray(canvas[:, lo:lo + bs])
                lg = forward_window(flat, win, lo, kv, dm)
                clg = forward_window(flat, win, lo, ckv, dm, True) \
                    if control else None
            else:
                cv = jnp.asarray(canvas)
                lg = forward_rows(flat, cv, lo, dm, bs)
                clg = forward_rows(flat, cv, lo, dm, bs, True) if control \
                    else None
            g, conf, cg = jax.device_get(
                _read(lg, jnp.asarray(block_served), clg))
            better = open_ & (g < gaps[:, cols])
            gaps[:, cols] = np.where(better, g, gaps[:, cols])
            cgaps[:, cols] = np.where(better, cg, cgaps[:, cols])
            for i in range(r):
                cand = np.flatnonzero(open_[i])
                for j in pick(conf[i], g[i], cand, n):
                    canvas[i, lo + j] = block_served[i, j]
                    open_[i, j] = False
        del lg, clg
    gaps[served == mask_id] = np.nan
    return gaps, cgaps
