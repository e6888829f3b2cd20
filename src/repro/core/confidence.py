"""Confidence scoring — the quantities every decoding strategy consumes.

Local confidence (Eq. 11): per masked position, the model's certainty about
its own argmax prediction, under three interchangeable metrics (the
heuristic baselines) — max probability, top-2 margin, negative entropy.

Global confidence (Eq. 10): the *foreseeing* term.  For a hypothetical next
state x_t, C_global = E_{p_θ} log p_θ(q, x_t) = -Σ_{j still masked} H_j —
the negative total predictive entropy of the state after the commitment.
Computing it requires ONE forward pass per candidate; FDM batches the K
candidates into the batch axis (one (K·B) forward instead of K sequential
queries — the TPU-native adaptation).

The hot reduction (B, L, V) -> four per-position scalars is served by the
fused Pallas kernel in ``repro.kernels`` when enabled; this module is the
pure-jnp reference semantics.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Scores(NamedTuple):
    """Per-position decode scores, each (B, L) float32."""
    argmax: jnp.ndarray      # int32 — candidate token per position
    max_prob: jnp.ndarray    # p(argmax)
    margin: jnp.ndarray      # p(top1) - p(top2)
    neg_entropy: jnp.ndarray  # Σ_v p log p  (≤ 0)


def pallas_enabled(dcfg=None) -> bool:
    """Resolve a DecodeConfig's ``use_pallas_kernel`` flag.

    ``None`` (the default) means auto: the fused kernel runs only on a real
    TPU backend — on CPU it would execute in Pallas interpret mode, whose
    Python-level emulation costs far more than the jnp reference it
    replaces.  ``True``/``False`` force the choice (tests use ``True`` to
    exercise the wiring through interpret mode).
    """
    flag = getattr(dcfg, "use_pallas_kernel", None) if dcfg is not None \
        else None
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


@jax.named_scope("confidence")
def score_logits(logits: jnp.ndarray,
                 use_kernel: bool = None) -> Scores:
    """One pass over the vocab axis -> all four per-position scores.

    ``use_kernel=True`` routes through the fused single-HBM-pass Pallas
    kernel (``repro.kernels.confidence.confidence_fused``); ``None`` keeps
    the pure-jnp reference (decode callers resolve their config flag via
    ``pallas_enabled`` and pass the result explicitly).
    """
    if use_kernel:
        from repro.kernels.confidence import confidence_fused
        a, p, m, e = confidence_fused(
            logits, interpret=jax.default_backend() != "tpu")
        return Scores(argmax=a, max_prob=p, margin=m, neg_entropy=e)
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    p = jnp.exp(logp)
    top2_p, top2_i = jax.lax.top_k(p, 2)
    neg_ent = jnp.sum(p * logp, axis=-1)
    return Scores(argmax=top2_i[..., 0].astype(jnp.int32),
                  max_prob=top2_p[..., 0],
                  margin=top2_p[..., 0] - top2_p[..., 1],
                  neg_entropy=neg_ent)


def score_logits_sharded(logits: jnp.ndarray) -> Scores:
    """score_logits variant built ONLY from axis reductions (max / argmax /
    masked re-max / sums) — every one partitions cleanly when the vocab
    axis is sharded (GSPMD turns them into per-shard reductions + a scalar
    combine), unlike ``top_k`` which forces a full-vocab all-gather
    (measured: 37 GiB of f32 logits gathered per prefill step, §Perf C2).
    """
    lf = logits.astype(jnp.float32)
    m1 = jnp.max(lf, axis=-1)
    a1 = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    # second max: mask out every occurrence of the max (ties -> margin 0)
    masked = jnp.where(lf >= m1[..., None], -jnp.inf, lf)
    m2 = jnp.max(masked, axis=-1)
    dup = jnp.sum((lf >= m1[..., None]).astype(jnp.int32), axis=-1) > 1
    m2 = jnp.where(dup, m1, m2)
    # stable softmax pieces
    s = jnp.sum(jnp.exp(lf - m1[..., None]), axis=-1)
    u = jnp.sum(lf * jnp.exp(lf - m1[..., None]), axis=-1)
    inv_s = 1.0 / s
    logz = m1 + jnp.log(s)
    max_prob = inv_s
    p2 = jnp.exp(m2 - m1) * inv_s
    neg_ent = u * inv_s - logz
    return Scores(argmax=a1, max_prob=max_prob,
                  margin=max_prob - p2, neg_entropy=neg_ent)


def local_confidence(scores: Scores, metric: str) -> jnp.ndarray:
    """The heuristic ranking score (higher = more confident), (B, L)."""
    if metric == "probability":
        return scores.max_prob
    if metric == "margin":
        return scores.margin
    if metric == "entropy":
        return scores.neg_entropy
    raise ValueError(f"unknown local-confidence metric {metric!r}")


def global_confidence(logits: jnp.ndarray, still_masked: jnp.ndarray
                      ) -> jnp.ndarray:
    """Eq. 10 over a *hypothetical next state*'s logits.

    logits (B, L, V) from the forward pass on the candidate-committed
    sequence; still_masked (B, L) marks positions masked in that state.
    Returns (B,) — Σ_j 1[masked] · Σ_v p log p  (negative total entropy).
    """
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    neg_ent = jnp.sum(jnp.exp(logp) * logp, axis=-1)          # (B, L)
    return jnp.sum(neg_ent * still_masked.astype(jnp.float32), axis=-1)
