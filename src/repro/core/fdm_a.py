"""FDM-A — Acceleration with the Foreseeing Decoding Method (Algorithm 2).

Three phases per step, decided per example from the max-probability profile
of the masked positions (η₁ > η₂ thresholds):

  * **exploration** — no position exceeds η₁: context is scarce, decode a
    single token with the full FDM search (K=K₁, γ=γ₁, n=1);
  * **acceleration** — ≥ N qualified positions (> η₁): context is ample,
    commit min(NUM, N) tokens local-only (FDM with K=1 ⇔ Eq. 18);
  * **balance** — qualified and borderline (η₂ < p ≤ η₁) coexist: commit
    NUM(>η₁) tokens with the foreseeing search over γ=η₂ survivors
    (Eq. 17); if no borderline tokens exist, local-only commit of the
    qualified set (Eq. between 17/18).

Batch handling: each example picks its phase independently (vectorized);
the K-candidate foreseeing forward runs once for the whole batch whenever
*any* example is in a search phase, and each example selects between the
search result and the local-only result.  The search forward is skipped
entirely when every example is in the acceleration phase — this is where
the paper's >3× TPS comes from.  Two implementations of that skip:

  * ``FDMAStrategy.step`` — host early-out (``bool(device_get(...))``), one
    scalar sync per step; used by the legacy host step loop.
  * ``FDMAStrategy.fused_step`` — a ``lax.cond`` over the batched phase
    plan; fully traceable, so the device-resident drivers
    (``core/loop.py``) can run it inside ``lax.while_loop`` with zero host
    syncs while XLA still executes only the taken branch at runtime.

Both variants accumulate the per-step phase histogram into the strategy
carry (a ``(4,)`` int32; see ``FDMAStrategy``), which is how
``SampleStats.phase_counts`` gets populated without extra device syncs.
``fdm_a_step`` / ``fdm_a_step_fused`` survive as carry-less wrappers for
the legacy step-function signature.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DecodeConfig, ModelConfig
from repro.core.confidence import pallas_enabled, score_logits
from repro.core.fdm import fdm_select
from repro.core.strategies import (ModelFn, Strategy, commit_topn,
                                   register_strategy)


@jax.named_scope("plan")
def fdm_a_plan(logits: jnp.ndarray, active: jnp.ndarray,
               dcfg: DecodeConfig):
    """Vectorized phase decision. Returns (n, gamma, need_search) per ex."""
    s = score_logits(logits, pallas_enabled(dcfg))
    p = jnp.where(active, s.max_prob, 0.0)
    qualified = p > dcfg.eta1
    borderline = (p > dcfg.eta2) & ~qualified
    q_cnt = jnp.sum(qualified, axis=-1)                        # (B,)
    b_cnt = jnp.sum(borderline, axis=-1)
    explore = q_cnt == 0
    accel = q_cnt >= dcfg.n_max
    local_only = (~explore) & (~accel) & (b_cnt == 0)
    balance = (~explore) & (~accel) & (b_cnt > 0)
    n = jnp.where(explore, 1, jnp.minimum(q_cnt, dcfg.n_max)).astype(jnp.int32)
    gamma = jnp.where(explore, dcfg.gamma1, dcfg.eta2).astype(jnp.float32)
    need_search = explore | balance
    return s, n, gamma, need_search, (explore, accel, local_only, balance)


PHASES = ("explore", "accel", "local_only", "balance")


def _phase_flags(phases) -> jnp.ndarray:
    """(4,) int32 per-step phase histogram: how many batch examples landed
    in each of Algorithm 2's phases this step (each example is in exactly
    one, so the flags sum to B)."""
    return jnp.stack([jnp.sum(p, dtype=jnp.int32) for p in phases])


def fdm_a_step(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
               dcfg: DecodeConfig, n_unused) -> Tuple[jnp.ndarray, int]:
    """Legacy carry-less entry point (host early-out variant)."""
    new_x, _, fwd = FDM_A.step(rng, jnp.zeros((4,), jnp.int32), x, active,
                               model_fn, cfg, dcfg, n_unused)
    return new_x, fwd


def fdm_a_step_fused(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
                     dcfg: DecodeConfig, n_unused
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Legacy carry-less entry point (trace-safe ``lax.cond`` variant)."""
    new_x, _, fwd = FDM_A.fused_step(rng, jnp.zeros((4,), jnp.int32), x,
                                     active, model_fn, cfg, dcfg, n_unused)
    return new_x, fwd


class FDMAStrategy(Strategy):
    """Algorithm 2 as a registered ``Strategy``: the strategy itself
    declares its fused form (the ``lax.cond`` early-out) instead of the
    loop driver special-casing it by name.

    The carry is a ``(4,)`` int32 per-phase step counter — each step adds
    the batch's phase histogram, so it rides the fused block/request
    carries to the end of decode and ``Decoder`` reads it back into
    ``SampleStats.phase_counts`` with zero extra syncs.  With batch 1 the
    counts sum to ``stats.steps`` exactly.
    """

    name = "fdm_a"
    carry_is_observational = True    # the counter never steers decoding
    trace_confidence_tap = True      # the scoring forward is unconditional
                                     # and full-canvas (the cond-guarded
                                     # search forward is K-folded, which
                                     # the tap's shape guard skips)

    def init_carry(self, cfg: ModelConfig, dcfg: DecodeConfig):
        return jnp.zeros((4,), jnp.int32)

    def forwards_per_step(self, dcfg: DecodeConfig) -> float:
        return 1.0 + dcfg.k1       # upper bound; the accel phase uses 1

    def phase_counts(self, carry) -> Dict[str, int]:
        vals = jax.device_get(carry)
        return {k: int(v) for k, v in zip(PHASES, vals)}

    def trace_phase(self, carry_before, carry_after):
        """The step's phase for the trace: each step adds the batch's
        phase histogram to the carry, so the argmax of the increment is
        the batch-dominant phase (exact at batch 1 — every example is in
        one phase)."""
        return jnp.argmax(carry_after - carry_before).astype(jnp.int32)

    def step(self, rng, carry, x, active, model_fn: ModelFn,
             cfg: ModelConfig, dcfg: DecodeConfig, n) -> Tuple:
        logits = model_fn(x)
        s, nn, gamma, need_search, phases = fdm_a_plan(logits, active, dcfg)
        carry = carry + _phase_flags(phases)

        # acceleration/local phases: plain local top-n commit (Eq. 18/K=1)
        x_local = commit_topn(x, s.max_prob, s.argmax, active, nn)

        # host early-out: skip the K-forward entirely if nobody searches
        if not bool(jax.device_get(jnp.any(need_search))):
            return x_local, carry, 1

        x_search, extra = fdm_select(x, logits, active, model_fn, cfg,
                                     k=dcfg.k1, gamma=gamma, n=nn,
                                     use_kernel=pallas_enabled(dcfg))
        new_x = jnp.where(need_search[:, None], x_search, x_local)
        return new_x, carry, 1 + extra

    def fused_step(self, rng, carry, x, active, model_fn: ModelFn,
                   cfg: ModelConfig, dcfg: DecodeConfig, n) -> Tuple:
        """Traceable FDM-A step: the acceleration-phase skip is a
        ``lax.cond`` on the batched phase plan instead of a host sync, so
        the whole step lives inside the device-resident loops.  Returns
        the forward count as a traced f32 scalar (1 when the search branch
        is skipped, 1 + K₁ when it runs) for the carry's stats counters.
        """
        logits = model_fn(x)
        s, nn, gamma, need_search, phases = fdm_a_plan(logits, active, dcfg)
        carry = carry + _phase_flags(phases)
        x_local = commit_topn(x, s.max_prob, s.argmax, active, nn)

        def with_search(_):
            x_search, extra = fdm_select(x, logits, active, model_fn, cfg,
                                         k=dcfg.k1, gamma=gamma, n=nn,
                                         use_kernel=pallas_enabled(dcfg))
            new_x = jnp.where(need_search[:, None], x_search, x_local)
            return new_x, jnp.float32(1 + extra)

        def local_only(_):
            return x_local, jnp.float32(1)

        new_x, fwd = jax.lax.cond(jnp.any(need_search), with_search,
                                  local_only, operand=None)
        return new_x, carry, fwd


FDM_A = FDMAStrategy()
register_strategy(FDM_A)
