"""Block assembly: one residual block per layer, built from config flags.

Families covered (bidirectional — diffusion LMs score every masked
position at once — except block-diffusion LMs, whose attention is
block-causal, ``ModelConfig.attn_block``):

* dense / vlm:      norm → attn → norm → MLP
* moe:              norm → attn → norm → MoE (shared + routed)
* ssm (xLSTM):      norm → {mLSTM | sLSTM}             (no separate FFN)
* hybrid (Hymba):   norm → [attn ∥ mamba] fused mean   → norm → MLP
* encdec decoder:   norm → self-attn → norm → cross-attn → norm → MLP

Every block has three entry points:
  ``forward``  — full-sequence train/prefill;
  ``decode``   — one token against per-layer state (KVCache / SSM state);
  ``init``     — parameter pytree.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_lib
from repro.models.attention import (attention_cached, attention_capture,
                                    attention_decode, attention_forward,
                                    attention_window, init_attention,
                                    init_cache)
from repro.models.layers import (Params, apply_mlp, apply_norm, init_mlp,
                                 init_norm)
from repro.models.moe import init_moe, moe_forward
from repro.parallel.ctx import constrain

LayerState = Any  # KVCache | ssm state | (KVCache, MambaState) | None


def _is_moe_layer(cfg: ModelConfig, idx: int) -> bool:
    return cfg.is_moe and idx >= cfg.moe.first_k_dense


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_block(rng, cfg: ModelConfig, idx: int) -> Params:
    ks = jax.random.split(rng, 6)
    if cfg.arch_type == "ssm":
        return {"norm1": init_norm(cfg),
                "mixer": ssm_lib.init_xlstm_layer(ks[0], cfg, idx)}
    p: Params = {"norm1": init_norm(cfg),
                 "attn": init_attention(ks[0], cfg),
                 "norm2": init_norm(cfg)}
    if cfg.arch_type == "hybrid":
        p["mamba"] = ssm_lib.init_mamba(ks[1], cfg)
        # learnable fusion of the two parallel head groups (Hymba mean-fuse
        # with per-path norm; we use per-path RMS scales)
        p["mix_attn"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["mix_ssm"] = jnp.ones((cfg.d_model,), jnp.float32)
    if _is_moe_layer(cfg, idx):
        p["moe"] = init_moe(ks[2], cfg)
    elif cfg.d_ff:
        p["mlp"] = init_mlp(ks[2], cfg)
    if cfg.is_encdec:
        p["norm_x"] = init_norm(cfg)
        p["xattn"] = init_attention(ks[3], cfg)
    return p


# --------------------------------------------------------------------------
# forward (train / prefill)
# --------------------------------------------------------------------------

def block_forward(p: Params, x, positions, cfg: ModelConfig, idx: int,
                  enc_out: Optional[jnp.ndarray] = None,
                  enc_positions: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,L,d) -> (x', aux_loss)."""
    # the residual stream is sequence-parallel between blocks (Megatron-SP)
    x = constrain(x, ("dp", "sp", None))
    aux = jnp.zeros((), jnp.float32)
    if cfg.arch_type == "ssm":
        h = apply_norm(p["norm1"], x, cfg)
        return x + ssm_lib.xlstm_forward(p["mixer"], h, cfg, idx), aux

    h = apply_norm(p["norm1"], x, cfg)
    attn_out = attention_forward(p["attn"], h, positions, cfg)
    if cfg.arch_type == "hybrid":
        ssm_out = ssm_lib.mamba_forward(p["mamba"], h, cfg)
        mixed = 0.5 * (attn_out * p["mix_attn"].astype(x.dtype)
                       + ssm_out * p["mix_ssm"].astype(x.dtype))
        x = x + mixed
    else:
        x = x + attn_out

    if cfg.is_encdec and enc_out is not None:
        h = apply_norm(p["norm_x"], x, cfg)
        x = x + _cross_attention(p["xattn"], h, enc_out, cfg)

    if _is_moe_layer(cfg, idx):
        h = apply_norm(p["norm2"], x, cfg)
        out, aux = moe_forward(p["moe"], h, cfg)
        x = x + out
    elif cfg.d_ff:
        h = apply_norm(p["norm2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x, aux


def _cross_attention(p: Params, x, enc_out, cfg: ModelConfig) -> jnp.ndarray:
    """Decoder query attends over encoder output (no RoPE on cross path)."""
    dt = x.dtype
    b, lq, _ = x.shape
    lk = enc_out.shape[1]
    hd, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q = (x @ p["wq"].astype(dt)).reshape(b, lq, nq, hd)
    k = (enc_out.astype(dt) @ p["wk"].astype(dt)).reshape(b, lk, nkv, hd)
    v = (enc_out.astype(dt) @ p["wv"].astype(dt)).reshape(b, lk, nkv, hd)
    from repro.models.attention import _sdpa
    out = _sdpa(q, k, v, None, hd ** -0.5)
    return out.reshape(b, lq, -1) @ p["wo"].astype(dt)


# --------------------------------------------------------------------------
# decode (single new token, per-layer state)
# --------------------------------------------------------------------------

def init_layer_state(cfg: ModelConfig, idx: int, batch: int, length: int,
                     dtype=jnp.bfloat16, valid_length=None) -> LayerState:
    if cfg.arch_type == "ssm":
        return ssm_lib.init_xlstm_state(cfg, idx, batch)
    kv = init_cache(cfg, batch, length, dtype, valid_length=valid_length)
    if cfg.arch_type == "hybrid":
        return (kv, ssm_lib.init_mamba_state(cfg, batch, dtype))
    return kv


def block_decode(p: Params, x, positions, cfg: ModelConfig, idx: int,
                 state: LayerState,
                 enc_out: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, LayerState]:
    """One token (B,1,d) against this layer's state."""
    if cfg.arch_type == "ssm":
        h = apply_norm(p["norm1"], x, cfg)
        out, st = ssm_lib.xlstm_step(p["mixer"], h, cfg, idx, state)
        return x + out, st

    h = apply_norm(p["norm1"], x, cfg)
    if cfg.arch_type == "hybrid":
        kv, ms = state
        attn_out, kv = attention_decode(p["attn"], h, positions, cfg, kv)
        ssm_out, ms = ssm_lib.mamba_step(p["mamba"], h, cfg, ms)
        x = x + 0.5 * (attn_out * p["mix_attn"].astype(x.dtype)
                       + ssm_out * p["mix_ssm"].astype(x.dtype))
        state = (kv, ms)
    else:
        attn_out, state = attention_decode(p["attn"], h, positions, cfg, state)
        x = x + attn_out

    if cfg.is_encdec and enc_out is not None:
        h = apply_norm(p["norm_x"], x, cfg)
        x = x + _cross_attention(p["xattn"], h, enc_out, cfg)

    if _is_moe_layer(cfg, idx):
        h = apply_norm(p["norm2"], x, cfg)
        out, _ = moe_forward(p["moe"], h, cfg, capacity_factor=2.0)
        x = x + out
    elif cfg.d_ff:
        h = apply_norm(p["norm2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x, state


# --------------------------------------------------------------------------
# fixed-shape block cache (cache_policy = prefix | dual; attention archs)
# --------------------------------------------------------------------------

def block_capture(p: Params, x, positions, cfg: ModelConfig, idx: int,
                  enc_out: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Any]:
    """Full-sequence forward that also emits this layer's fixed-shape
    K/V cache (prefill / block-boundary refresh).  Attention-backed
    archs only — recurrent state cannot ride a scatter-style cache; the
    Decoder gates ssm/hybrid out before ever reaching here."""
    x = constrain(x, ("dp", "sp", None))
    h = apply_norm(p["norm1"], x, cfg)
    attn_out, kv = attention_capture(p["attn"], h, positions, cfg)
    x = x + attn_out

    if cfg.is_encdec and enc_out is not None:
        h = apply_norm(p["norm_x"], x, cfg)
        x = x + _cross_attention(p["xattn"], h, enc_out, cfg)

    if _is_moe_layer(cfg, idx):
        h = apply_norm(p["norm2"], x, cfg)
        out, _ = moe_forward(p["moe"], h, cfg, capacity_factor=2.0)
        x = x + out
    elif cfg.d_ff:
        h = apply_norm(p["norm2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x, kv


def block_cached(p: Params, x, positions, cfg: ModelConfig, idx: int,
                 cache, win_start,
                 enc_out: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """A W-row live window (B, W, d) against this layer's full-length
    cache; read-only with respect to the cache (refresh = block_capture)."""
    h = apply_norm(p["norm1"], x, cfg)
    x = x + attention_cached(p["attn"], h, positions, cfg, cache, win_start)

    if cfg.is_encdec and enc_out is not None:
        h = apply_norm(p["norm_x"], x, cfg)
        x = x + _cross_attention(p["xattn"], h, enc_out, cfg)

    if _is_moe_layer(cfg, idx):
        h = apply_norm(p["norm2"], x, cfg)
        out, _ = moe_forward(p["moe"], h, cfg, capacity_factor=2.0)
        x = x + out
    elif cfg.d_ff:
        h = apply_norm(p["norm2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x


# --------------------------------------------------------------------------
# window decode (W tokens vs frozen prefix — cached semi-AR sampling)
# --------------------------------------------------------------------------

def block_window(p: Params, x, positions, cfg: ModelConfig, idx: int,
                 state: LayerState, enc_out: Optional[jnp.ndarray] = None,
                 extend: Optional[str] = None
                 ) -> Tuple[jnp.ndarray, LayerState]:
    """W tokens (B, W, d) against this layer's frozen prefix state.

    ``extend`` selects which half of the state a commit pass updates:
      None         — pure scoring (within-block denoising steps);
      "kv"         — append the window's k/v to the attention cache
                     (callers pass the LIVE window incl. future masks so
                     the cached k/v carry bidirectional context, then
                     reset the valid length to the committed block);
      "recurrent"  — advance the causal recurrent states (xLSTM/mamba)
                     over the window (callers pass the committed block
                     ONLY — causal mixers never see the future anyway).
    """
    if cfg.arch_type == "ssm":
        h = apply_norm(p["norm1"], x, cfg)
        if extend == "recurrent":
            out, st2 = ssm_lib.xlstm_forward(p["mixer"], h, cfg, idx,
                                             state=state, return_state=True)
            return x + out, st2
        out = ssm_lib.xlstm_forward(p["mixer"], h, cfg, idx, state=state)
        return x + out, state

    h = apply_norm(p["norm1"], x, cfg)
    if cfg.arch_type == "hybrid":
        kv, ms = state
        attn_out, kv = attention_window(p["attn"], h, positions, cfg, kv,
                                        extend=extend == "kv")
        if extend == "recurrent":
            ssm_out, ms = ssm_lib.mamba_forward(p["mamba"], h, cfg,
                                                state=ms, return_state=True)
        else:
            ssm_out = ssm_lib.mamba_forward(p["mamba"], h, cfg, state=ms)
        x = x + 0.5 * (attn_out * p["mix_attn"].astype(x.dtype)
                       + ssm_out * p["mix_ssm"].astype(x.dtype))
        state = (kv, ms)
    else:
        attn_out, state = attention_window(p["attn"], h, positions, cfg,
                                           state, extend=extend == "kv")
        x = x + attn_out

    if cfg.is_encdec and enc_out is not None:
        h = apply_norm(p["norm_x"], x, cfg)
        x = x + _cross_attention(p["xattn"], h, enc_out, cfg)

    if _is_moe_layer(cfg, idx):
        h = apply_norm(p["norm2"], x, cfg)
        out, _ = moe_forward(p["moe"], h, cfg, capacity_factor=2.0)
        x = x + out
    elif cfg.d_ff:
        h = apply_norm(p["norm2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x, state
