"""Mixture-of-Experts: top-k router + grouped expert matmuls.

Two dispatch paths share the router:

* **Dropless** (no activation mesh: the path ``Decoder`` and the server
  run on one device).  The layer holds the experts
  ``MoEConfig.held_first ..`` (all by default; one chip's share under
  expert parallelism), routes every token over all ``num_experts``, and
  computes only the (token, expert) pairs whose expert it holds: the
  pairs are sorted by expert and each projection is one grouped matmul
  over the held experts' weights with the groups' sizes (the Pallas
  megablox ``gmm`` on TPU, ``jax.lax.ragged_dot`` elsewhere).  No token
  is dropped and no expert is padded.  While a decode runner counts
  (``expert_counter``), each call adds its computed pairs, the held
  experts that got a row, and one call.
* **Capacity** (under an activation mesh, ``launch/dryrun.py``), below.

Capacity-path design notes
--------------------------
* Dispatch is **sort + gather** (never scatter): tokens are argsorted by
  expert id, each expert reads a contiguous capacity-C slice, and the combine
  step gathers each token's expert output back by its inverse-permutation
  rank.  Gathers shard cleanly under GSPMD; with the expert axis on the
  ``model`` mesh axis the dispatch/combine lower to all-to-all.
* Expert FFNs are a single batched einsum over stacked weights
  ``(E, d, ff)`` — one big MXU-friendly contraction instead of E separate
  matmuls.
* Capacity ``C = ceil(T·k/E · capacity_factor)`` rounded up to a multiple of
  128 (MXU lane alignment); overflow tokens are dropped (their combine weight
  is zeroed), matching Switch/GShard semantics.
* Covers Mixtral (8e top-2), DeepSeek-V2 (2 shared + 160 routed top-6,
  first layer dense) and LLaDA-MoE styles from one config.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init, init_mlp, apply_mlp
from repro.parallel.ctx import constrain, option


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def capacity(num_tokens: int, cfg: ModelConfig, factor: float = 1.25) -> int:
    e, k = cfg.moe.num_experts, cfg.moe.num_experts_per_tok
    c = int(num_tokens * k * factor / e) + 1
    return max(_round_up(c, 128), 128)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_moe(rng, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, ff = cfg.d_model, m.moe_d_ff
    ks = jax.random.split(rng, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, m.num_experts), scale=0.02),
        # stacked weights of the held experts: SwiGLU gate/up/down
        "w_gate": dense_init(ks[1], (m.num_held, d, ff)),
        "w_up": dense_init(ks[2], (m.num_held, d, ff)),
        "w_down": dense_init(ks[3], (m.num_held, ff, d)),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=ff * m.num_shared_experts)
    return p


# --------------------------------------------------------------------------
# routing
# --------------------------------------------------------------------------

def router_topk(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """logits (T, E) -> (gates (T, k) normalized, expert_ids (T, k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, ids


def load_balance_loss(logits: jnp.ndarray, ids: jnp.ndarray,
                      num_experts: int) -> jnp.ndarray:
    """Switch-style aux loss: E · Σ_e f_e · P_e  (+ router z-loss)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T, E)
    frac_routed = jnp.mean(
        jax.nn.one_hot(ids, num_experts, dtype=jnp.float32), axis=(0, 1))
    frac_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac_routed * frac_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32),
                                             axis=-1)))
    return aux + 1e-3 * z


# --------------------------------------------------------------------------
# dropless forward (one device, the held experts)
# --------------------------------------------------------------------------

_COUNTS: contextvars.ContextVar = contextvars.ContextVar("expert_counts",
                                                         default=None)


@contextlib.contextmanager
def expert_counter(cfg: ModelConfig):
    """Count the dropless layer's work while tracing inside the block.

    Yields ``read() -> (3,) int32`` — the (token, held expert) pairs
    computed, the held experts that got at least one row (summed over
    calls), and the expert-layer calls — for an MoE ``cfg``, to be
    called before the block ends, inside the same trace; ``read()`` is
    ``None`` for other configs, whose programs stay as they were.  The
    counts live in a ``jax.Ref``, so the layer adds to them from inside
    the layer scan, the decode's ``while_loop`` and the search's
    ``lax.cond`` alike."""
    if not cfg.is_moe:
        yield lambda: None
        return
    ref = jax.new_ref(jnp.zeros((3,), jnp.int32))
    token = _COUNTS.set(ref)
    try:
        yield lambda: ref[...]
    finally:
        _COUNTS.reset(token)


# the kernel's row tile; each step reads one expert's whole matrix.  At
# SDAR-30B-A3B's widths on one v5e (48 layers of 16 held experts, three
# projections) 64 rows took 295 us a layer at 64-1024 buffered pairs and
# 658 us at 32768, against 346/673 us at 128 rows, 450/753 us at 256,
# 1277 us at (128, 128, 128) tiles and 785/1162 us for jax.lax.ragged_dot
GMM_ROWS = 64


def _gmm(x: jnp.ndarray, w: jnp.ndarray,
         group_sizes: jnp.ndarray) -> jnp.ndarray:
    """The TPU branch: the megablox kernel over ``x`` padded to whole
    ``GMM_ROWS`` tiles (its differentiable entry point: a train step
    traces both branches' derivatives)."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import gmm
    m = x.shape[0]
    xp = jnp.pad(x, ((0, -m % GMM_ROWS), (0, 0)))
    return gmm(xp, w, group_sizes, x.dtype,
               (GMM_ROWS, w.shape[1], w.shape[2]))[:m]


def _ragged_dot(x: jnp.ndarray, w: jnp.ndarray,
                group_sizes: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``x`` (M, k), sorted by group, times their group's matrix
    of ``w`` (G, k, n); rows past ``sum(group_sizes)`` are left undefined
    (the TPU kernel visits only the tiles that hold a group's rows).  The
    platform the program is lowered for picks the kernel: megablox
    ``gmm`` on TPU, ``jax.lax.ragged_dot`` elsewhere."""
    return jax.lax.platform_dependent(x, w, group_sizes, tpu=_gmm,
                                      default=_ragged_dot)


@jax.named_scope("experts")
def _dropless(p: Params, tokens: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (T, d) -> (the held experts' part of the output (T, d),
    aux loss)."""
    m = cfg.moe
    t, d = tokens.shape
    k, held = m.num_experts_per_tok, m.num_held
    dt = tokens.dtype

    logits = tokens @ p["router"].astype(dt)                    # (T, E)
    gates, ids = router_topk(logits, k)                         # (T, k)
    aux = load_balance_loss(logits, ids, m.num_experts) * m.router_aux_coef

    # pairs to experts held elsewhere sort last, under the id ``held``
    local = ids.reshape(t * k) - m.held_first
    mine = (local >= 0) & (local < held)
    local = jnp.where(mine, local, held)
    order = jnp.argsort(local, stable=True)                     # (Tk,)
    rank = jnp.argsort(order, stable=True)                      # inverse
    sizes = jnp.sum(jax.nn.one_hot(local, held, dtype=jnp.int32), axis=0)

    xs = jnp.take(tokens, order // k, axis=0)                   # (Tk, d)
    h = jax.nn.silu(grouped_matmul(xs, p["w_gate"].astype(dt), sizes)) \
        * grouped_matmul(xs, p["w_up"].astype(dt), sizes)
    ys = grouped_matmul(h, p["w_down"].astype(dt), sizes)       # (Tk, d)

    # back to (token, slot) order; pairs not held read zero (their rows
    # lie past the groups and were never computed)
    ys = jnp.where(mine[:, None], jnp.take(ys, rank, axis=0), 0)
    out = jnp.sum(ys.reshape(t, k, d) * gates[..., None].astype(dt),
                  axis=1)
    counts = _COUNTS.get()
    if counts is not None:
        counts[...] += jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                                  jnp.int32(1)])
    return out, aux


# --------------------------------------------------------------------------
# capacity forward (under an activation mesh)
# --------------------------------------------------------------------------

def _dispatch(p: Params, tokens: jnp.ndarray, cfg: ModelConfig,
              capacity_factor: float, grouped: bool
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (T, d) -> (out (T, d), aux).  The sort-based dispatch core;
    ``grouped=True`` means we run under vmap (shard-local) and must not
    emit sharding constraints (specs would mismatch the batched rank)."""
    m = cfg.moe
    t, d = tokens.shape
    k = m.num_experts_per_tok
    e = m.num_experts
    dt = tokens.dtype

    logits = tokens @ p["router"].astype(dt)                    # (T, E)
    gates, ids = router_topk(logits, k)                         # (T, k)
    aux = load_balance_loss(logits, ids, e) * m.router_aux_coef

    c = capacity(t, cfg, capacity_factor)
    flat_e = ids.reshape(t * k)                                 # (Tk,)
    order = jnp.argsort(flat_e, stable=True)                    # (Tk,)
    rank = jnp.argsort(order, stable=True)                      # inverse perm
    counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])        # (E,)

    # expert slot grid reads contiguous sorted slices
    slot_idx = offsets[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    slot_valid = jnp.arange(c, dtype=jnp.int32)[None, :] < counts[:, None]
    tok_of_sorted = order // k                                  # (Tk,) token id
    gathered_tok = jnp.take(tok_of_sorted, jnp.clip(slot_idx, 0, t * k - 1),
                            axis=0)                             # (E, C)
    xs = jnp.take(tokens, gathered_tok.reshape(-1), axis=0)
    xs = xs.reshape(e, c, d) * slot_valid[..., None].astype(dt)
    if not grouped:
        # expert parallelism: the dispatch becomes an all-to-all on the
        # model axis when E divides it (guarded inside constrain)
        xs = constrain(xs, ("tp", None, None))

    # batched SwiGLU over experts — single MXU contraction per weight
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["w_gate"].astype(dt),
                                preferred_element_type=jnp.float32).astype(dt))
         * jnp.einsum("ecd,edf->ecf", xs, p["w_up"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt))
    ys = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)

    # combine: (token, slot) j sits at expert flat_e[j], position s_of[j]
    s_of = rank - jnp.take(offsets, flat_e)                     # (Tk,)
    in_cap = s_of < c
    flat_out = ys[flat_e, jnp.clip(s_of, 0, c - 1)]             # (Tk, d) gather
    flat_out = flat_out * in_cap[:, None].astype(dt)
    out = jnp.sum(flat_out.reshape(t, k, d)
                  * gates[..., None].astype(dt), axis=1)        # (T, d)
    return out, aux


def moe_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                capacity_factor: float = 1.25
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, L, d) -> (out (B, L, d), aux_loss scalar).

    Without an activation mesh: the dropless layer over the held experts
    (``capacity_factor`` does not apply).  Under one: sort-based
    capacity dispatch, gather-only data movement, batched expert einsums.

    SHARD-LOCAL dispatch (§Perf iteration A1): the argsort is global over
    all T tokens, which GSPMD can only realize by replicating the token
    stream (measured: 156 GiB/dev on mixtral × train_4k).  Under an
    activation mesh we therefore group tokens by their (data × seq-shard)
    grid cell — a transpose/reshape that is shard-layout-exact — and vmap
    the dispatch over groups: every sort/scatter becomes shard-local,
    exactly like torch-MoE's per-rank dispatch, and each group meets its
    own capacity independently (standard expert-parallel semantics).
    """
    from repro.parallel.ctx import shard_counts
    m = cfg.moe
    b, l, d = x.shape
    t = b * l
    if option("mesh") is None:
        out, aux = _dropless(p, x.reshape(t, d), cfg)
        out = out.reshape(b, l, d)
        if m.num_shared_experts:
            out = out + apply_mlp(p["shared"], x.reshape(t, d),
                                  cfg).reshape(b, l, d)
        return out, aux
    gd, gm = shard_counts()
    # expert-parallel-capable configs (E divides the model axis — DeepSeek)
    # keep the GLOBAL dispatch: its all-to-all is the efficient path, and
    # grouping would run all E experts per group at padded capacity
    # (measured +80% collective on deepseek train, §Perf B1-refuted).
    # Grouping is for the fallback topology (Mixtral: 8 experts on 16).
    try:
        from repro.parallel.ctx import _STATE as _ctx_state
        msize = (_ctx_state["mesh"].shape["model"]
                 if _ctx_state["mesh"] is not None else 1)
    except Exception:
        msize = 1
    if msize > 1 and m.num_experts % msize == 0:
        gd = gm = 1
    g = gd * gm

    # grouped dispatch pays off only when each group has enough tokens to
    # fill expert capacity tiles; tiny decode batches (T/g « capacity
    # rounding) measured +91% collective from padding — keep those global
    if g > 1 and b % gd == 0 and l % gm == 0 and (t // g) >= 1024:
        xg = x.reshape(gd, b // gd, gm, l // gm, d)
        xg = xg.transpose(0, 2, 1, 3, 4).reshape(g, t // g, d)
        xg = constrain(xg, ("grid", None, None))
        out_g, aux_g = jax.vmap(
            lambda tk: _dispatch(p, tk, cfg, capacity_factor, True))(xg)
        out_g = constrain(out_g, ("grid", None, None))
        out = out_g.reshape(gd, gm, b // gd, l // gm, d) \
            .transpose(0, 2, 1, 3, 4).reshape(b, l, d)
        aux = jnp.mean(aux_g)
    else:
        out, aux = _dispatch(p, x.reshape(t, d), cfg, capacity_factor,
                             False)
        out = out.reshape(b, l, d)

    if m.num_shared_experts:
        out = out + apply_mlp(p["shared"], x.reshape(t, d),
                              cfg).reshape(b, l, d)
    return out, aux
