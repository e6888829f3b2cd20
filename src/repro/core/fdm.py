"""FDM — the Foreseeing Decoding Method (Algorithm 1).

Per step:
  1. one forward pass scores every masked position; the argmax token of each
     masked position is its *candidate*;
  2. candidates with local confidence p ≤ γ are pruned (dynamic pruning);
  3. the Top-K surviving candidates by C_local form the search set Λ;
  4. **foreseeing**: each λ ∈ Λ is committed into a hypothetical next state;
     all K states are evaluated in ONE batched forward pass (the K candidate
     sequences are folded into the batch axis — the TPU-native replacement
     for the paper's sequential A100 re-queries; semantics of Eq. 15 are
     unchanged, only the schedule);
  5. commit the candidate maximizing C_local + C_global (Eq. 15); if Λ is
     empty, fall back to the pure-local argmax commit.

Generalization to n > 1 tokens per step (used by FDM-A's balance phase):
the top (n-1) candidates by C_local are committed unconditionally (they
would win any local tie-break) and the K candidates ranked n-1 … n+K-2
compete for the last slot via the foreseeing criterion.  With n=1 this is
exactly Algorithm 1.  Recorded as an interpretation choice in DESIGN.md.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DecodeConfig, ModelConfig
from repro.core.confidence import (global_confidence, pallas_enabled,
                                   score_logits)
from repro.core.strategies import (NEG, ModelFn, StatelessStrategy,
                                   commit_topn, rank_desc, register_strategy)


def fdm_select(x: jnp.ndarray, logits: jnp.ndarray, active: jnp.ndarray,
               model_fn: ModelFn, cfg: ModelConfig, k: int,
               gamma, n, use_kernel: bool = None) -> Tuple[jnp.ndarray, int]:
    """The FDM search core. gamma/n may be scalars or (B,) arrays.

    Returns (new_x, extra_forward_count).
    """
    b, l = x.shape
    s = score_logits(logits, use_kernel)
    gamma_arr = jnp.broadcast_to(jnp.asarray(gamma, jnp.float32), (b,))
    n_arr = jnp.broadcast_to(jnp.asarray(n, jnp.int32), (b,))

    c_local_log = jnp.log(jnp.maximum(s.max_prob, 1e-30))     # Eq. 11

    # Λ construction: prune p ≤ γ, rank by C_local, keep K contenders for
    # the n-th slot; the first n-1 slots are the unconditional "safe set".
    eligible = active & (s.max_prob > gamma_arr[:, None])
    conf_el = jnp.where(eligible, s.max_prob, NEG)
    ranks_el = rank_desc(conf_el)
    safe = eligible & (ranks_el < (n_arr - 1)[:, None])
    contender = eligible & (ranks_el >= (n_arr - 1)[:, None]) \
        & (ranks_el < (n_arr - 1 + k)[:, None])
    has_search = jnp.any(contender, axis=-1)                  # Λ ≠ ∅ per ex.

    x_safe = jnp.where(safe, s.argmax, x)

    # build the K hypothetical next states: commit contender slot j
    # (j-th contender in C_local order) on top of the safe set — one
    # broadcast one-hot build, no per-candidate Python loop
    slot = ranks_el - (n_arr - 1)[:, None]                    # contender slot
    sel_k = contender[None] & \
        (slot[None] == jnp.arange(k)[:, None, None])          # (K, B, L)
    xc = jnp.where(sel_k, s.argmax[None], x_safe[None])       # (K, B, L)
    valid = jnp.any(sel_k, axis=-1)                           # (K, B)

    # ONE batched foreseeing forward over all K candidates
    with jax.named_scope("search"):
        logits_c = model_fn(xc.reshape(k * b, l)).reshape(k, b, l, -1)
        still_masked = (xc == cfg.mask_token_id)
        c_glob = jax.vmap(global_confidence)(logits_c,
                                             still_masked)    # (K, B)
    with jax.named_scope("commit"):
        c_loc = jnp.sum(jnp.where(sel_k, c_local_log[None], 0.0), axis=-1)
        total = jnp.where(valid, c_loc + c_glob, NEG)         # Eq. 15
        winner = jnp.argmax(total, axis=0)                    # (B,)
        win_commit = jnp.take_along_axis(
            sel_k, winner[None, :, None], axis=0)[0]          # (B, L)
        x_search = jnp.where(win_commit, s.argmax, x_safe)

    # Λ = ∅ fallback: pure local top-n commit (no γ filter)
    x_local = commit_topn(x, s.max_prob, s.argmax, active, n_arr)
    with jax.named_scope("commit"):
        new_x = jnp.where(has_search[:, None], x_search, x_local)
    return new_x, k   # K batch-equivalent foreseeing forwards


def fdm_step(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
             dcfg: DecodeConfig, n) -> Tuple[jnp.ndarray, int]:
    """Algorithm 1 with the paper defaults: n=1 token per step."""
    logits = model_fn(x)
    new_x, extra = fdm_select(x, logits, active, model_fn, cfg,
                              k=dcfg.k, gamma=dcfg.gamma, n=1,
                              use_kernel=pallas_enabled(dcfg))
    return new_x, 1 + extra


class FDMStrategy(StatelessStrategy):
    """Algorithm 1 as a registered ``Strategy`` (stateless; the step is
    fully traceable, so the fused form is the step itself)."""

    def __init__(self):
        super().__init__("fdm", fdm_step)

    def forwards_per_step(self, dcfg: DecodeConfig) -> float:
        return 1.0 + dcfg.k        # scoring forward + K-candidate search


register_strategy(FDMStrategy())
