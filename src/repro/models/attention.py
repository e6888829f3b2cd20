"""Attention for bidirectional masked-diffusion LMs.

Three attention families, each with a full (train/prefill) path and a cached
single-token decode path:

* **GQA / MHA** — standard grouped-query attention, optional per-head q/k
  RMSNorm (Qwen3) and RoPE variants (standard / half / mrope / none).
* **Sliding-window** — bidirectional band mask ``|i-j| < window`` (the
  diffusion adaptation of Mixtral's causal SWA); the decode path keeps only a
  window-sized KV cache, which is the sub-quadratic route for ``long_500k``.
* **MLA** (DeepSeek-V2) — queries/keys/values factored through low-rank
  latents.  Train path materializes per-head K/V; the decode path runs in
  *absorbed* form against the compressed ``c_kv`` cache (512+64 dims per
  position instead of H·(d_qk+d_v)), which is the whole point of MLA and maps
  directly onto the TPU memory hierarchy (the latent cache stays in HBM, the
  absorbed weight products live in VMEM-resident tiles).

LLDMs score all masked positions at once, so attention is bidirectional,
except in block-diffusion LMs (SDAR): there ``cfg.attn_block`` > 0 and a
position sees its own block and the blocks before it (``block_mask``), in
the full path and in both ops of the fixed-shape block cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (Params, apply_rope, dense_init,
                                 rms_norm_headwise)
from repro.parallel.ctx import constrain


class KVCache(NamedTuple):
    """Frozen-prefix KV cache for semi-AR diffusion decode.

    ``k``/``v``: (B, S, n_kv, hd) for GQA; for MLA ``k`` holds the compressed
    latent (B, S, kv_lora) and ``v`` the rope key (B, S, qk_rope).  ``length``
    is the number of valid positions (static in the dry-run contract).
    """
    k: jnp.ndarray
    v: jnp.ndarray
    length: int


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    if cfg.attention == "mla":
        m = cfg.mla
        ks = jax.random.split(rng, 7)
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq_a": dense_init(ks[0], (d, m.q_lora_rank)),
            "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
            "wq_b": dense_init(ks[1], (m.q_lora_rank, nq * qk)),
            "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
            "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
            "wk_b": dense_init(ks[3], (m.kv_lora_rank, nq * m.qk_nope_head_dim)),
            "wv_b": dense_init(ks[4], (m.kv_lora_rank, nq * m.v_head_dim)),
            "wo": dense_init(ks[5], (nq * m.v_head_dim, d)),
        }
        return p
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq * hd)),
        "wk": dense_init(ks[1], (d, nkv * hd)),
        "wv": dense_init(ks[2], (d, nkv * hd)),
        "wo": dense_init(ks[3], (nq * hd, d)),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((hd,), jnp.float32)
        p["k_scale"] = jnp.ones((hd,), jnp.float32)
    return p


# --------------------------------------------------------------------------
# masks
# --------------------------------------------------------------------------

def band_mask(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, window: int) -> jnp.ndarray:
    """Bidirectional sliding-window band: attend iff |i-j| < window."""
    diff = q_pos[..., :, None] - kv_pos[..., None, :]
    return jnp.abs(diff) < window


def block_mask(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, block: int
               ) -> jnp.ndarray:
    """Block-causal: attend iff j // block <= i // block, blocks counted
    from position 0."""
    return kv_pos[..., None, :] // block <= q_pos[..., :, None] // block


def position_mask(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, window: int = 0,
                  block: int = 0) -> Optional[jnp.ndarray]:
    """The band and block-causal masks of absolute positions, combined;
    None where attention is unmasked."""
    mask = None
    if window:
        mask = band_mask(q_pos, kv_pos, window)
    if block:
        bm = block_mask(q_pos, kv_pos, block)
        mask = bm if mask is None else mask & bm
    return mask


SDPA_CHUNK = 1024   # q-chunk for the memory-efficient long-sequence path


def self_attention(q, k, v, scale: float, window: int = 0,
                   chunk: int = SDPA_CHUNK, block: int = 0) -> jnp.ndarray:
    """Full self-attention without materializing (L, L).

    Short sequences take the dense path; long ones scan q in chunks of
    ``chunk`` so the live score tensor is (B, H, chunk, L) — the
    memory-efficient jnp equivalent of the Pallas flash kernel (which
    serves the same role on real TPU hardware).  Band and block-causal
    masks are computed per chunk from index arithmetic, never as an
    (L, L) bool.
    """
    b, l, h, dh = q.shape
    if l <= chunk:
        mask = position_mask(jnp.arange(l), jnp.arange(l), window, block)
        return _sdpa(q, k, v, mask, scale)
    pad = (-l) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nch = q.shape[1] // chunk
    qc = q.reshape(b, nch, chunk, h, dh).swapaxes(0, 1)   # (nch,B,C,H,dh)
    kpos = jnp.arange(l)

    def body(_, xs):
        qch, start = xs
        mask = position_mask(start + jnp.arange(chunk), kpos, window,
                             block)                       # (C, L) only
        return None, _sdpa(qch, k, v, mask, scale)

    starts = jnp.arange(nch, dtype=jnp.int32) * chunk
    _, outs = jax.lax.scan(body, None, (qc, starts))
    out = outs.swapaxes(0, 1).reshape(b, nch * chunk, h, -1)
    return out[:, :l]


def _sdpa(q, k, v, mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q: (B, Lq, H, dh), k/v: (B, Lk, G, dh_{k,v}); grouped heads broadcast.

    Scores accumulate in f32; returns q.dtype.
    """
    b, lq, h, dh = q.shape
    g = k.shape[2]
    rep = h // g
    qg = q.reshape(b, lq, g, rep, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        # mask (Lq, Lk) broadcasts directly; (B, Lq, Lk) gets head axes
        if mask.ndim == 3:
            mask = mask[:, None, None]
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, lq, h, v.shape[-1])


# --------------------------------------------------------------------------
# GQA full + decode
# --------------------------------------------------------------------------

def _project_qkv(p: Params, x, positions, cfg: ModelConfig):
    dt = x.dtype
    b, l, _ = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    from repro.parallel.ctx import option
    if option("xgather") and l > 1:
        # gather the (small, bf16) attention input ONCE instead of letting
        # GSPMD all-gather q, k and v separately after projection: one
        # d-wide gather replaces (nq+2·nkv)·hd-wide ones (§Perf C5)
        x = constrain(x, ("dp", None, None))
    q_spec = kv_spec = ("dp", None, "tp", None)
    if option("seq_attn") and l > 1:
        # sequence-parallel attention: q stays seq-sharded (no q gather —
        # each device attends its own seq chunk with ALL heads against
        # gathered k/v).  The natural layout for bidirectional models.
        q_spec = ("dp", "sp", None, None)
        kv_spec = ("dp", None, None, None)
    q = constrain((x @ p["wq"].astype(dt)).reshape(b, l, nq, hd), q_spec)
    k = constrain((x @ p["wk"].astype(dt)).reshape(b, l, nkv, hd), kv_spec)
    v = constrain((x @ p["wv"].astype(dt)).reshape(b, l, nkv, hd), kv_spec)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_scale"])
        k = rms_norm_headwise(k, p["k_scale"])
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)
    return q, k, v


def gqa_forward(p: Params, x, positions, cfg: ModelConfig,
                attn_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full attention over x (B, L, d)."""
    q, k, v = _project_qkv(p, x, positions, cfg)
    out = self_attention(q, k, v, cfg.head_dim ** -0.5,
                         window=cfg.sliding_window, block=cfg.attn_block)
    out = constrain(out.reshape(*x.shape[:2], -1), ("dp", None, "tp"))
    # NOTE (§Perf C3, refuted & reverted): forcing the row-parallel product
    # to the sequence-parallel layout here (reduce-scatter instead of
    # all-reduce) measured neutral on qwen3 prefill and +43% collective on
    # deepseek train — GSPMD's own choice is better; leave it free.
    return out @ p["wo"].astype(x.dtype)


def gqa_decode(p: Params, x, positions, cfg: ModelConfig,
               cache: KVCache) -> Tuple[jnp.ndarray, KVCache]:
    """One new token (B, 1, d) against a frozen cache of capacity S.

    The new k/v are written IN PLACE (``dynamic_update_slice`` + buffer
    donation — no concat copy of a 32k/500k cache per layer), then the
    token attends bidirectionally over the valid prefix.  Sliding-window
    configs keep a window-sized ring buffer, the O(W) route for long_500k.
    """
    q, k_new, v_new = _project_qkv(p, x, positions, cfg)
    pos0 = positions[0, 0] if positions.ndim == 2 else positions[0, 0, 0]
    cap = cache.k.shape[1]
    slot = (pos0 % cap) if cfg.sliding_window else jnp.minimum(pos0, cap - 1)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype),
                                            slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype),
                                            slot, axis=1)
    valid = jnp.arange(cap) <= pos0          # ring buffer: all valid once warm
    out = _sdpa(q, k.astype(x.dtype), v.astype(x.dtype), valid[None, None],
                cfg.head_dim ** -0.5)
    out = out.reshape(*x.shape[:2], -1) @ p["wo"].astype(x.dtype)
    return out, KVCache(k=k, v=v, length=cache.length + 1)


def gqa_window(p: Params, x, positions, cfg: ModelConfig, cache: KVCache,
               extend: bool = False) -> Tuple[jnp.ndarray, KVCache]:
    """A W-token window attends [valid frozen prefix | itself] (Fast-dLLM-
    style cached semi-AR decoding; sampler scale, so the concat is cheap).

    ``extend=True`` additionally writes the window's k/v into the cache at
    the current valid length (used once per committed block)."""
    dt = x.dtype
    w = x.shape[1]
    q, k_new, v_new = _project_qkv(p, x, positions, cfg)
    cap = cache.k.shape[1]
    length = cache.length
    k = jnp.concatenate([cache.k.astype(dt), k_new], axis=1)
    v = jnp.concatenate([cache.v.astype(dt), v_new], axis=1)
    valid = jnp.concatenate([jnp.arange(cap) < length,
                             jnp.ones((w,), bool)])
    out = _sdpa(q, k, v, valid[None, None], cfg.head_dim ** -0.5)
    out = out.reshape(*x.shape[:2], -1) @ p["wo"].astype(x.dtype)
    if extend:
        k2 = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new.astype(cache.k.dtype), length, axis=1)
        v2 = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), length, axis=1)
        cache = KVCache(k=k2, v=v2, length=length + w)
    return out, cache


def mla_window(p: Params, x, positions, cfg: ModelConfig, cache: KVCache,
               extend: bool = False) -> Tuple[jnp.ndarray, KVCache]:
    """Window attention against the compressed MLA latent cache (per-head
    K/V reconstructed from the valid latents — fine at sampler scale)."""
    m = cfg.mla
    dt = x.dtype
    b, w, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_rope, c_new, kr_new = _mla_latents(p, x, positions, cfg)
    cap = cache.k.shape[1]
    length = cache.length
    c_all = jnp.concatenate([cache.k.astype(dt), c_new], axis=1)
    kr_all = jnp.concatenate([cache.v.astype(dt), kr_new], axis=1)
    s = cap + w
    k_nope = (c_all @ p["wk_b"].astype(dt)).reshape(b, s, nq,
                                                    m.qk_nope_head_dim)
    vv = (c_all @ p["wv_b"].astype(dt)).reshape(b, s, nq, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                  (b, s, nq, m.qk_rope_head_dim))], axis=-1)
    valid = jnp.concatenate([jnp.arange(cap) < length,
                             jnp.ones((w,), bool)])
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = _sdpa(q, k, vv, valid[None, None], scale)
    out = out.reshape(b, w, -1) @ p["wo"].astype(dt)
    if extend:
        c2 = jax.lax.dynamic_update_slice_in_dim(
            cache.k, c_new.astype(cache.k.dtype), length, axis=1)
        kr2 = jax.lax.dynamic_update_slice_in_dim(
            cache.v, kr_new.astype(cache.v.dtype), length, axis=1)
        cache = KVCache(k=c2, v=kr2, length=length + w)
    return out, cache


@jax.named_scope("attention")
def attention_window(p: Params, x, positions, cfg: ModelConfig,
                     cache: KVCache, extend: bool = False
                     ) -> Tuple[jnp.ndarray, KVCache]:
    if cfg.attention == "mla":
        return mla_window(p, x, positions, cfg, cache, extend)
    return gqa_window(p, x, positions, cfg, cache, extend)


# --------------------------------------------------------------------------
# fixed-shape block cache (cache_policy = prefix | dual)
# --------------------------------------------------------------------------
#
# Unlike the shrinking-window path above (variable cache length, host-side
# valid-length bookkeeping), these two entry points keep every shape static
# so they can ride the fused drivers: the cache always covers ALL ``total``
# positions of the canvas, and the live window writes its fresh K/V into a
# functional copy at a *traced* offset.  No validity mask is needed —
# attention is bidirectional and every column is context: cached outside
# the window, freshly recomputed inside it.

def gqa_capture(p: Params, x, positions, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, KVCache]:
    """Full attention that also returns the K/V it computed — the
    prefill/refresh op of the fixed-shape block cache."""
    q, k, v = _project_qkv(p, x, positions, cfg)
    out = self_attention(q, k, v, cfg.head_dim ** -0.5,
                         window=cfg.sliding_window, block=cfg.attn_block)
    out = constrain(out.reshape(*x.shape[:2], -1), ("dp", None, "tp"))
    # length is an array so the cache stacks/slices cleanly across the
    # per-group layer axis (it is never consulted: the cache is always full)
    return (out @ p["wo"].astype(x.dtype),
            KVCache(k=k, v=v, length=jnp.int32(x.shape[1])))


def gqa_cached(p: Params, x, positions, cfg: ModelConfig, cache: KVCache,
               win_start) -> jnp.ndarray:
    """A W-row live window attends over the full fixed-length cache with
    its own fresh K/V scattered in at traced ``win_start`` (read-only with
    respect to the cache — refreshes go through ``gqa_capture``)."""
    dt = x.dtype
    w = x.shape[1]
    q, k_new, v_new = _project_qkv(p, x, positions, cfg)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k.astype(dt), k_new,
                                            win_start, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v.astype(dt), v_new,
                                            win_start, axis=1)
    mask = position_mask(win_start + jnp.arange(w),
                         jnp.arange(cache.k.shape[1]), cfg.sliding_window,
                         cfg.attn_block)
    out = _sdpa(q, k, v, mask, cfg.head_dim ** -0.5)
    out = out.reshape(*x.shape[:2], -1)
    return out @ p["wo"].astype(dt)


def mla_capture(p: Params, x, positions, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, KVCache]:
    """Materialized MLA forward returning the latent cache (c_kv, k_rope)."""
    m = cfg.mla
    dt = x.dtype
    b, l, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_latents(p, x, positions, cfg)
    k_nope = (c_kv @ p["wk_b"].astype(dt)).reshape(b, l, nq,
                                                   m.qk_nope_head_dim)
    v = (c_kv @ p["wv_b"].astype(dt)).reshape(b, l, nq, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, l, nq, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = self_attention(q, k, v, scale)
    return (out.reshape(b, l, -1) @ p["wo"].astype(dt),
            KVCache(k=c_kv, v=k_rope, length=jnp.int32(l)))


def mla_cached(p: Params, x, positions, cfg: ModelConfig, cache: KVCache,
               win_start) -> jnp.ndarray:
    """Live window against the fixed-length MLA latent cache (per-head K/V
    reconstructed from all latents — fine at sampler scale)."""
    m = cfg.mla
    dt = x.dtype
    b, w, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_rope, c_new, kr_new = _mla_latents(p, x, positions, cfg)
    c_all = jax.lax.dynamic_update_slice_in_dim(cache.k.astype(dt), c_new,
                                                win_start, axis=1)
    kr_all = jax.lax.dynamic_update_slice_in_dim(cache.v.astype(dt), kr_new,
                                                 win_start, axis=1)
    s = c_all.shape[1]
    k_nope = (c_all @ p["wk_b"].astype(dt)).reshape(b, s, nq,
                                                    m.qk_nope_head_dim)
    vv = (c_all @ p["wv_b"].astype(dt)).reshape(b, s, nq, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                  (b, s, nq, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = _sdpa(q, k, vv, None, scale)
    return out.reshape(b, w, -1) @ p["wo"].astype(dt)


@jax.named_scope("attention")
def attention_capture(p: Params, x, positions, cfg: ModelConfig
                      ) -> Tuple[jnp.ndarray, KVCache]:
    if cfg.attention == "mla":
        return mla_capture(p, x, positions, cfg)
    return gqa_capture(p, x, positions, cfg)


@jax.named_scope("attention")
def attention_cached(p: Params, x, positions, cfg: ModelConfig,
                     cache: KVCache, win_start) -> jnp.ndarray:
    if cfg.attention == "mla":
        return mla_cached(p, x, positions, cfg, cache, win_start)
    return gqa_cached(p, x, positions, cfg, cache, win_start)


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# --------------------------------------------------------------------------

def _mla_latents(p: Params, x, positions, cfg: ModelConfig):
    """Shared front half: query heads + compressed kv latent + rope key."""
    m = cfg.mla
    dt = x.dtype
    b, l, _ = x.shape
    nq = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_lat = rms_norm_headwise(x @ p["wq_a"].astype(dt), p["q_norm"])
    q = constrain((q_lat @ p["wq_b"].astype(dt)).reshape(b, l, nq, qk),
                  ("dp", None, "tp", None))
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg, head_dim=m.qk_rope_head_dim)

    kv = x @ p["wkv_a"].astype(dt)                     # (B, L, kv_lora + rope)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm_headwise(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg,
                        head_dim=m.qk_rope_head_dim)[:, :, 0]   # shared head
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(p: Params, x, positions, cfg: ModelConfig,
                attn_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Materialized MLA for train/prefill (per-head K/V from the latent)."""
    m = cfg.mla
    dt = x.dtype
    b, l, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_latents(p, x, positions, cfg)
    k_nope = constrain((c_kv @ p["wk_b"].astype(dt))
                       .reshape(b, l, nq, m.qk_nope_head_dim),
                       ("dp", None, "tp", None))
    v = constrain((c_kv @ p["wv_b"].astype(dt))
                  .reshape(b, l, nq, m.v_head_dim),
                  ("dp", None, "tp", None))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, l, nq, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = self_attention(q, k, v, scale)
    return out.reshape(b, l, -1) @ p["wo"].astype(dt)


def mla_decode(p: Params, x, positions, cfg: ModelConfig,
               cache: KVCache) -> Tuple[jnp.ndarray, KVCache]:
    """Absorbed-form MLA decode against the compressed latent cache.

    cache.k = c_kv (B, S, kv_lora), cache.v = k_rope (B, S, qk_rope).
    Scores:  q_nope·W_UKᵀ ⟶ latent-space query (per head), dotted with c_kv;
    Output:  attn·c_kv absorbed through W_UV.  Never materializes per-head
    K/V over the 32k/500k cache — the decisive memory saving.
    """
    m = cfg.mla
    dt = x.dtype
    b, l, _ = x.shape
    nq = cfg.num_heads
    q_nope, q_rope, c_new, kr_new = _mla_latents(p, x, positions, cfg)
    pos0 = positions[0, 0] if positions.ndim == 2 else positions[0, 0, 0]
    cap = cache.k.shape[1]
    slot = jnp.minimum(pos0, cap - 1)
    c_kv = jax.lax.dynamic_update_slice_in_dim(
        cache.k, c_new.astype(cache.k.dtype), slot, axis=1).astype(dt)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache.v, kr_new.astype(cache.v.dtype), slot, axis=1).astype(dt)
    valid = (jnp.arange(cap) <= pos0).astype(jnp.float32)

    # absorb W_UK into the query: q_lat (B,1,H,r)
    wk_b = p["wk_b"].astype(dt).reshape(m.kv_lora_rank, nq, m.qk_nope_head_dim)
    q_lat = jnp.einsum("blhd,rhd->blhr", q_nope, wk_b,
                       preferred_element_type=jnp.float32).astype(dt)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("blhr,bsr->bhls", q_lat, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("blhd,bsd->bhls", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(valid[None, None, None] > 0, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhls,bsr->blhr", w, c_kv,
                       preferred_element_type=jnp.float32).astype(dt)
    wv_b = p["wv_b"].astype(dt).reshape(m.kv_lora_rank, nq, m.v_head_dim)
    out = jnp.einsum("blhr,rhd->blhd", o_lat, wv_b,
                     preferred_element_type=jnp.float32).astype(dt)
    out = out.reshape(b, l, -1) @ p["wo"].astype(dt)
    return out, KVCache(k=c_kv, v=k_rope, length=cache.length + 1)


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

@jax.named_scope("attention")
def attention_forward(p: Params, x, positions, cfg: ModelConfig,
                      attn_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if cfg.attention == "mla":
        return mla_forward(p, x, positions, cfg, attn_mask)
    return gqa_forward(p, x, positions, cfg, attn_mask)


@jax.named_scope("attention")
def attention_decode(p: Params, x, positions, cfg: ModelConfig,
                     cache: KVCache) -> Tuple[jnp.ndarray, KVCache]:
    if cfg.attention == "mla":
        return mla_decode(p, x, positions, cfg, cache)
    return gqa_decode(p, x, positions, cfg, cache)


def init_cache(cfg: ModelConfig, batch: int, length: int,
               dtype=jnp.bfloat16,
               valid_length: Optional[int] = None) -> KVCache:
    """Allocate (or spec) the decode cache for one layer.

    ``valid_length`` overrides the initial valid count (0 for the cached
    sampler, which fills the buffer block by block; default = ``length``,
    the dry-run contract of a fully warmed cache)."""
    vl = length if valid_length is None else valid_length
    if cfg.attention == "mla":
        m = cfg.mla
        return KVCache(k=jnp.zeros((batch, length, m.kv_lora_rank), dtype),
                       v=jnp.zeros((batch, length, m.qk_rope_head_dim), dtype),
                       length=vl)
    eff = min(length, cfg.sliding_window) if cfg.sliding_window else length
    return KVCache(
        k=jnp.zeros((batch, eff, cfg.num_kv_heads, cfg.head_dim), dtype),
        v=jnp.zeros((batch, eff, cfg.num_kv_heads, cfg.head_dim), dtype),
        length=vl)
