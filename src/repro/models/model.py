"""DiffusionLM: the model zoo's single entry point.

A masked-diffusion LM over any assigned architecture: bidirectional forward
that scores **all** positions (masked-token prediction head), plus a cached
single-token ``decode_step`` for the serving shapes.

Compile-time design: layers with identical parameter structure are **stacked
and scanned** (``lax.scan`` over the layer axis) instead of unrolled — an
80-layer qwen2-vl lowers as one scanned block body, which keeps dry-run
compiles tractable and is exactly how production JAX LMs (MaxText) do it.
Heterogeneous stacks (DeepSeek's first-dense-layer, xLSTM's s/m pattern)
are grouped into homogeneous runs, each scanned.

Modality frontends are STUBS per the assignment contract: ``audio_stub``
(whisper) consumes precomputed frame embeddings via the encoder stack;
``vision_stub`` (qwen2-vl) prepends precomputed patch embeddings to the
token stream with M-RoPE position ids.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as blocks_lib
from repro.models.layers import (Params, apply_norm, compute_dtype,
                                 embed_tokens, init_embed, init_norm, lm_head)


# --------------------------------------------------------------------------
# layer grouping (homogeneous runs -> stacked scan)
# --------------------------------------------------------------------------

def _layer_groups(cfg: ModelConfig) -> List[List[int]]:
    """Partition layer indices into maximal runs with identical param trees."""
    def sig(idx: int) -> str:
        s = ""
        if cfg.arch_type == "ssm":
            from repro.models.ssm import xlstm_kind
            s += xlstm_kind(cfg, idx)
        s += "M" if (cfg.is_moe and idx >= cfg.moe.first_k_dense) else "D"
        return s

    groups: List[List[int]] = []
    for i in range(cfg.num_layers):
        if groups and sig(groups[-1][-1]) == sig(i):
            groups[-1].append(i)
        else:
            groups.append([i])
    return groups


def _stack(trees: List[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked: Params, layer: Params, i) -> Params:
    return jax.tree.map(lambda s, a: s.at[i].set(a), stacked, layer)


def _init_stacked(init_layer: Callable[[int], Params], n: int) -> Params:
    """Stack ``n`` layers from ``init_layer(j)`` without holding the group
    twice: each layer is built exactly as before, written into the donated
    stacked buffer, and dropped — at full width the stack of a group is
    most of the device's memory."""
    first = init_layer(0)
    out = jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), first)
    out = _put_layer(out, first, 0)
    del first
    for j in range(1, n):
        out = _put_layer(out, init_layer(j), j)
    return out


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def encoder_config(cfg: ModelConfig) -> ModelConfig:
    """The whisper-style encoder is a dense bidirectional stack."""
    return dataclasses.replace(
        cfg, arch_type="dense", num_layers=cfg.encdec.encoder_layers,
        encdec=None, sliding_window=0, remat=cfg.remat)


def init_model(rng, cfg: ModelConfig) -> Params:
    ks = jax.random.split(rng, cfg.num_layers + 3)
    params: Params = {"embed": init_embed(ks[0], cfg),
                      "norm_f": init_norm(cfg)}
    groups = _layer_groups(cfg)
    params["blocks"] = [
        _init_stacked(lambda j, g=g: blocks_lib.init_block(
            ks[1 + g[j]], cfg, g[j]), len(g))
        for g in groups]
    if cfg.is_encdec:
        ecfg = encoder_config(cfg)
        eks = jax.random.split(ks[-1], ecfg.num_layers + 1)
        params["encoder"] = {
            "blocks": [_init_stacked(lambda j, g=g: blocks_lib.init_block(
                eks[g[j]], ecfg, g[j]), len(g))
                for g in _layer_groups(ecfg)],
            "norm_f": init_norm(ecfg),
        }
    if cfg.encdec is not None and cfg.encdec.frontend == "vision_stub":
        # projector from stub patch embeddings to d_model (the one trained
        # piece of the vision path; the ViT itself is out of scope per spec)
        params["projector"] = {
            "w": jax.random.normal(ks[-2], (cfg.d_model, cfg.d_model),
                                   jnp.float32) * (cfg.d_model ** -0.5)}
    return params


# --------------------------------------------------------------------------
# positions
# --------------------------------------------------------------------------

def make_positions(cfg: ModelConfig, batch: int, length: int,
                   offset: int = 0, num_patches: int = 0) -> jnp.ndarray:
    """Position ids; (3,B,L) for M-RoPE (t/h/w streams: patches get a 2-d
    grid in h/w and constant t; text advances t only — Qwen2-VL scheme)."""
    pos = offset + jnp.arange(length, dtype=jnp.int32)[None].repeat(batch, 0)
    if cfg.rope != "mrope":
        return pos
    side = max(int(num_patches ** 0.5), 1)
    t = jnp.where(pos < num_patches, 0, pos - num_patches + 1)
    hh = jnp.where(pos < num_patches, (pos % (side * side)) // side, t)
    ww = jnp.where(pos < num_patches, pos % side, t)
    return jnp.stack([t, hh, ww])


# --------------------------------------------------------------------------
# forward (train / prefill): score every position
# --------------------------------------------------------------------------

def _run_stack(block_groups, x, positions, cfg: ModelConfig,
               groups: List[List[int]], enc_out=None):
    """Scan each homogeneous group of stacked layers."""
    aux_total = jnp.zeros((), jnp.float32)
    for g_params, g_idx in zip(block_groups, groups):
        rep_idx = g_idx[0]   # any layer in the group has the same structure

        def body(carry, layer_params):
            h, aux = carry
            h2, a = blocks_lib.block_forward(layer_params, h, positions, cfg,
                                             rep_idx, enc_out=enc_out)
            return (h2, aux + a), None

        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        if len(g_idx) == 1:
            (x, aux_total), _ = body((x, aux_total),
                                     jax.tree.map(lambda a: a[0], g_params))
        elif cfg.unroll:
            for i in range(len(g_idx)):
                (x, aux_total), _ = body(
                    (x, aux_total), jax.tree.map(lambda a: a[i], g_params))
        else:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), g_params)
    return x, aux_total


def encode(params: Params, enc_embeds: jnp.ndarray,
           cfg: ModelConfig) -> jnp.ndarray:
    """Run the encoder stack over stub frame embeddings (B, S_enc, d)."""
    ecfg = encoder_config(cfg)
    b, l, _ = enc_embeds.shape
    pos = make_positions(ecfg, b, l)
    x = enc_embeds.astype(compute_dtype(cfg))
    x, _ = _run_stack(params["encoder"]["blocks"], x, pos, ecfg,
                      _layer_groups(ecfg))
    return apply_norm(params["encoder"]["norm_f"], x, ecfg)


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            enc_embeds: Optional[jnp.ndarray] = None,
            patch_embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            return_hidden: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, L) -> (logits (B, L, V) float32, aux_loss scalar).

    Bidirectional: every (masked or committed) position is scored.
    ``return_hidden=True`` skips the LM head and returns the final hidden
    states instead (callers that reduce logits chunk-wise — prefill
    scoring — avoid materializing (B, L, V) in one piece).
    """
    b, l = tokens.shape
    num_patches = 0
    x = embed_tokens(params["embed"], tokens, cfg,
                     positions=jnp.arange(l)[None].repeat(b, 0))
    if patch_embeds is not None:
        proj = patch_embeds.astype(x.dtype) @ \
            params["projector"]["w"].astype(x.dtype)
        x = jnp.concatenate([proj, x], axis=1)
        num_patches = patch_embeds.shape[1]
    if positions is None:
        positions = make_positions(cfg, b, x.shape[1],
                                   num_patches=num_patches)
    enc_out = None
    if cfg.is_encdec and enc_embeds is not None:
        enc_out = encode(params, enc_embeds, cfg)
    x, aux = _run_stack(params["blocks"], x, positions, cfg,
                        _layer_groups(cfg), enc_out=enc_out)
    x = apply_norm(params["norm_f"], x, cfg)
    if num_patches:
        x = x[:, num_patches:]
    if return_hidden:
        return x, aux
    logits = lm_head(params["embed"], x, cfg)
    return logits, aux


# --------------------------------------------------------------------------
# decode (one token against per-layer caches/states)
# --------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Per-group stacked layer states + the scalar position cursor."""
    layer_states: Tuple[Any, ...]
    enc_out: Optional[jnp.ndarray]


def init_decode_state(cfg: ModelConfig, batch: int, length: int,
                      dtype=jnp.bfloat16,
                      enc_out: Optional[jnp.ndarray] = None,
                      valid_length: Optional[int] = None) -> DecodeState:
    groups = _layer_groups(cfg)
    states = []
    for g in groups:
        sts = [blocks_lib.init_layer_state(cfg, i, batch, length, dtype,
                                           valid_length=valid_length)
               for i in g]
        states.append(_stack(sts))   # leading layer axis (len(g), ...)
    return DecodeState(layer_states=tuple(states), enc_out=enc_out)


def decode_step(params: Params, token: jnp.ndarray, position: jnp.ndarray,
                state: DecodeState, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, DecodeState]:
    """token (B, 1) at ``position`` (B, 1) -> (logits (B,1,V), new state)."""
    b = token.shape[0]
    x = embed_tokens(params["embed"], token, cfg, positions=position)
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(position[None], (3, b, 1))
    else:
        positions = position
    groups = _layer_groups(cfg)
    new_states = []
    for g_params, g_states, g_idx in zip(params["blocks"],
                                         state.layer_states, groups):
        rep_idx = g_idx[0]

        def body(h, scan_in):
            layer_params, layer_state = scan_in
            h2, st2 = blocks_lib.block_decode(layer_params, h, positions, cfg,
                                              rep_idx, layer_state,
                                              enc_out=state.enc_out)
            return h2, st2

        if len(g_idx) == 1:
            one = jax.tree.map(lambda a: a[0], (g_params, g_states))
            x, st2 = body(x, one)
            new_states.append(jax.tree.map(lambda a: a[None], st2))
        elif cfg.unroll:
            sts = []
            for i in range(len(g_idx)):
                one = jax.tree.map(lambda a: a[i], (g_params, g_states))
                x, st2 = body(x, one)
                sts.append(st2)
            new_states.append(
                jax.tree.map(lambda *xs: jnp.stack(xs), *sts))
        else:
            x, sts = jax.lax.scan(body, x, (g_params, g_states))
            new_states.append(sts)
    x = apply_norm(params["norm_f"], x, cfg)
    logits = lm_head(params["embed"], x, cfg)
    return logits, DecodeState(layer_states=tuple(new_states),
                               enc_out=state.enc_out)


# --------------------------------------------------------------------------
# fixed-shape block cache (cache_policy = prefix | dual)
# --------------------------------------------------------------------------

@jax.named_scope("capture_cache")
def capture_cache(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                  enc_out: Optional[jnp.ndarray] = None) -> DecodeState:
    """One full bidirectional pass over the canvas (B, total) capturing
    every layer's fixed-shape K/V — the prefill / block-boundary refresh
    op of the block cache (DESIGN.md "The KV cache").  Skips the LM head:
    refresh logits are never consumed (the next windowed forward
    re-scores the live rows anyway).  Unlike ``init_decode_state`` +
    extend, the cache always covers ALL ``total`` positions, so every
    shape stays static and the result can ride a ``lax.scan`` carry."""
    b, l = tokens.shape
    x = embed_tokens(params["embed"], tokens, cfg,
                     positions=jnp.arange(l)[None].repeat(b, 0))
    pos = make_positions(cfg, b, l)
    groups = _layer_groups(cfg)
    states = []
    for g_params, g_idx in zip(params["blocks"], groups):
        rep_idx = g_idx[0]

        def body(h, layer_params):
            return blocks_lib.block_capture(layer_params, h, pos, cfg,
                                            rep_idx, enc_out=enc_out)

        if len(g_idx) == 1:
            x, kv = body(x, jax.tree.map(lambda a: a[0], g_params))
            states.append(jax.tree.map(lambda a: a[None], kv))
        elif cfg.unroll:
            kvs = []
            for i in range(len(g_idx)):
                x, kv = body(x, jax.tree.map(lambda a: a[i], g_params))
                kvs.append(kv)
            states.append(jax.tree.map(lambda *xs: jnp.stack(xs), *kvs))
        else:
            x, kvs = jax.lax.scan(body, x, g_params)
            states.append(kvs)
    return DecodeState(layer_states=tuple(states), enc_out=enc_out)


@jax.named_scope("forward_cached")
def forward_cached(params: Params, tokens: jnp.ndarray, win_start,
                   state: DecodeState, cfg: ModelConfig) -> jnp.ndarray:
    """Score a W-row live window (B, W) at traced offset ``win_start``
    against the fixed-shape cache from ``capture_cache``.  Read-only with
    respect to the cache: each layer scatters its fresh window K/V into a
    functional copy and attends over all ``total`` keys — cached context
    outside the window, fresh inside.  Returns logits (B, W, V)."""
    b, w = tokens.shape
    epos = win_start + jnp.arange(w, dtype=jnp.int32)[None].repeat(b, 0)
    x = embed_tokens(params["embed"], tokens, cfg, positions=epos)
    if cfg.rope == "mrope":
        # slice the full-canvas position ids so cached and fresh K agree
        total = state.layer_states[0].k.shape[2]
        pos = jax.lax.dynamic_slice_in_dim(
            make_positions(cfg, b, total), win_start, w, axis=-1)
    else:
        pos = epos
    groups = _layer_groups(cfg)
    for g_params, g_states, g_idx in zip(params["blocks"],
                                         state.layer_states, groups):
        rep_idx = g_idx[0]

        def body(h, scan_in):
            layer_params, layer_cache = scan_in
            h2 = blocks_lib.block_cached(layer_params, h, pos, cfg, rep_idx,
                                         layer_cache, win_start,
                                         enc_out=state.enc_out)
            return h2, None

        if len(g_idx) == 1:
            one = jax.tree.map(lambda a: a[0], (g_params, g_states))
            x, _ = body(x, one)
        elif cfg.unroll:
            for i in range(len(g_idx)):
                one = jax.tree.map(lambda a: a[i], (g_params, g_states))
                x, _ = body(x, one)
        else:
            x, _ = jax.lax.scan(body, x, (g_params, g_states))
    x = apply_norm(params["norm_f"], x, cfg)
    return lm_head(params["embed"], x, cfg)


def set_valid_length(state: DecodeState, length) -> DecodeState:
    """Reset the attention caches' valid count (after a live-window "kv"
    extend wrote k/v for future-mask positions beyond the commit)."""
    from repro.models.attention import KVCache

    def fix(st):
        if isinstance(st, KVCache):
            return st._replace(length=jnp.full_like(st.length, length))
        if isinstance(st, tuple) and len(st) == 2 \
                and isinstance(st[0], KVCache):
            return (st[0]._replace(length=jnp.full_like(st[0].length,
                                                        length)), st[1])
        return st

    return DecodeState(
        layer_states=tuple(fix(s) for s in state.layer_states),
        enc_out=state.enc_out)


def forward_window(params: Params, tokens: jnp.ndarray,
                   positions: jnp.ndarray, state: DecodeState,
                   cfg: ModelConfig, extend: Optional[str] = None
                   ) -> Tuple[jnp.ndarray, DecodeState]:
    """Score a W-token window (B, W) against the frozen prefix state —
    the cached semi-AR sampling path (Fast-dLLM-style): within-block
    denoising re-scores only the active block, committed blocks live in
    the per-layer caches/recurrent states.  ``extend=True`` appends the
    window to the prefix (once per committed block)."""
    b, w = tokens.shape
    x = embed_tokens(params["embed"], tokens, cfg, positions=positions)
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(positions[None], (3, b, w))
    else:
        pos = positions
    groups = _layer_groups(cfg)
    new_states = []
    for g_params, g_states, g_idx in zip(params["blocks"],
                                         state.layer_states, groups):
        rep_idx = g_idx[0]

        def body(h, scan_in):
            layer_params, layer_state = scan_in
            h2, st2 = blocks_lib.block_window(layer_params, h, pos, cfg,
                                              rep_idx, layer_state,
                                              enc_out=state.enc_out,
                                              extend=extend)
            return h2, st2

        if len(g_idx) == 1:
            one = jax.tree.map(lambda a: a[0], (g_params, g_states))
            x, st2 = body(x, one)
            new_states.append(jax.tree.map(lambda a: a[None], st2))
        else:
            x, sts = jax.lax.scan(body, x, (g_params, g_states))
            new_states.append(sts)
    x = apply_norm(params["norm_f"], x, cfg)
    logits = lm_head(params["embed"], x, cfg)
    return logits, DecodeState(layer_states=tuple(new_states),
                               enc_out=state.enc_out)
