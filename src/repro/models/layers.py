"""Shared layers: norms, embeddings, RoPE variants, MLPs.

Pure-functional style: ``init_*`` builds a params dict, the matching apply
function consumes it.  Params are stored float32 and cast to the compute dtype
at use sites; all matmuls accumulate in float32 via ``preferred_element_type``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.parallel.ctx import constrain

Params = dict


def compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(rng, shape, scale: Optional[float] = None):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(rng, -3, 3, shape, jnp.float32))


def matmul(x, w, dtype):
    return jax.lax.dot_general(
        x.astype(dtype), w.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:            # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Per-head q/k norm (Qwen3): x (..., head_dim), scale (head_dim,)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary / positional embeddings
# --------------------------------------------------------------------------

def rope_frequencies(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def _rotate(x, cos, sin):
    # x: (..., rot_dim) pairs interleaved as [x0..x_{d/2-1} | x_{d/2}..x_{d-1}]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, positions, cfg: ModelConfig, head_dim: Optional[int] = None):
    """x: (B, L, H, hd); positions: (B, L) int32 or (3, B, L) for mrope.

    Variants: 'standard' rotates the full head_dim, 'half' (ChatGLM 2d RoPE)
    rotates the first half only, 'mrope' (Qwen2-VL) splits the rotary dims
    into (t, h, w) sections each driven by its own position stream,
    'sinusoidal'/'none' are no-ops here (absolute embedding added at embed).
    """
    if cfg.rope in ("none", "sinusoidal"):
        return x
    hd = head_dim or x.shape[-1]
    if cfg.rope == "half":
        rot_dim = hd // 2
    else:
        rot_dim = hd
    if cfg.rope == "mrope":
        secs = cfg.mrope_sections
        assert sum(secs) == rot_dim // 2, (secs, rot_dim)
        pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions[None], (3,) + positions.shape)
        inv = rope_frequencies(rot_dim, cfg.rope_theta)          # (rot/2,)
        # section s of the frequency axis uses position stream s
        sec_ids = jnp.repeat(jnp.arange(3), jnp.array(secs),
                             total_repeat_length=rot_dim // 2)    # (rot/2,)
        pos_per_freq = jnp.take(pos3, sec_ids, axis=0)            # (rot/2,B,L)
        ang = jnp.einsum("fbl,f->blf", pos_per_freq.astype(jnp.float32), inv)
    else:
        inv = rope_frequencies(rot_dim, cfg.rope_theta)
        ang = positions.astype(jnp.float32)[..., None] * inv      # (B,L,rot/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    return jnp.concatenate([_rotate(xr, cos, sin), xp], axis=-1)


def sinusoidal_embedding(length: int, dim: int) -> jnp.ndarray:
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# --------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# --------------------------------------------------------------------------

def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "silu":
        return {"gate": dense_init(ks[0], (d, ff)),
                "up": dense_init(ks[1], (d, ff)),
                "down": dense_init(ks[2], (ff, d))}
    return {"fc1": dense_init(ks[0], (d, ff)),
            "fc2": dense_init(ks[1], (ff, d))}


@jax.named_scope("mlp")
def apply_mlp(p: Params, x, cfg: ModelConfig):
    dt = x.dtype
    ff_spec = ("dp", None, "tp") if x.ndim == 3 else (None, "tp")
    if "gate" in p:
        h = jax.nn.silu(matmul(x, p["gate"], dt)) * matmul(x, p["up"], dt)
        return matmul(constrain(h, ff_spec), p["down"], dt)
    h = jax.nn.gelu(matmul(x, p["fc1"], dt))
    return matmul(constrain(h, ff_spec), p["fc2"], dt)


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------

def init_embed(rng, cfg: ModelConfig) -> Params:
    ks = jax.random.split(rng, 3)
    p = {"tok": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size))
    if cfg.rope == "sinusoidal":
        p["pos"] = sinusoidal_embedding(cfg.max_seq_len, cfg.d_model)
    return p


@jax.named_scope("embed")
def embed_tokens(p: Params, tokens, cfg: ModelConfig, positions=None):
    x = jnp.take(p["tok"], tokens, axis=0).astype(compute_dtype(cfg))
    if "pos" in p and positions is not None:
        pos1 = positions if positions.ndim == 2 else positions[0]
        x = x + jnp.take(p["pos"], pos1, axis=0).astype(x.dtype)
    return x


@jax.named_scope("lm_head")
def lm_head(p: Params, x, cfg: ModelConfig, vocab_sharded: bool = False):
    """``vocab_sharded=True`` keeps the logits sharded on the vocab axis
    (consumers must use reduction-only scoring, see
    ``core.confidence.score_logits_sharded``); the default sequence-
    parallel layout keeps the training loss's label gather vocab-local."""
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = jax.lax.dot_general(
        x.astype(compute_dtype(cfg)), w.astype(compute_dtype(cfg)),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # logits in f32
    if logits.ndim == 3:
        logits = constrain(logits, ("dp", None, "tp") if vocab_sharded
                           else ("dp", "sp", None))
    return logits
