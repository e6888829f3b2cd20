"""Decoding strategies: the ``Strategy`` protocol, the registry, and the
paper's comparison set.

A strategy is a first-class object (not a bare step function) so it can
carry per-decode state, declare its own fused (trace-safe) form, and plug
into the ``Decoder`` block loop (``core/decoder.py``) without touching the
sampler.  The protocol:

  * ``init_carry(cfg, dcfg) -> carry`` — per-decode state threaded through
    every step and across blocks.  Must be a fixed-shape pytree (it rides
    the ``lax.while_loop`` carry on the fused path); ``()`` for stateless
    strategies.  Strategies whose carry is *positional* (per canvas
    column) override ``init_carry_shaped`` instead and set
    ``positional_carry = True`` — see that method's docstring for the
    required ``(positional, global)`` carry structure.
  * ``begin_block(carry, x, in_block) -> carry`` — traceable block-entry
    hook, fired by every driver before a block's first step (WINO
    revocation uses it to drop cross-block pending commits so streaming
    stays final-commit-only).  Default: identity.
  * ``step(rng, carry, x, active, model_fn, cfg, dcfg, n)
    -> (new_x, new_carry, forwards)`` — one denoising step.  May touch the
    host (sync, early-out) — this is the variant the legacy host loop runs.
  * ``fused_step(...)`` — same signature, fully traceable (safe inside
    ``lax.while_loop``); defaults to ``step``.  Override when ``step``
    needs host control flow (FDM-A's early-out becomes a ``lax.cond``).
  * host-side stats: ``phase_counts(carry)`` and ``carry_stats(carry)``
    read observational counters (phase histograms, revocation and
    skipped-forward counts) out of the *final* carry into ``SampleStats``.
  * metadata: ``supports_fused`` (has a trace-safe form at all),
    ``forwards_per_step(dcfg)`` (nominal batched-forward count per step —
    an upper bound for adaptive strategies), ``carry_is_observational``
    and ``positional_carry`` (see the attribute comments).

Registered strategies (``register_strategy`` / ``resolve_strategy``):

* Heuristics (§2, Table 2): Random / Probability / Margin / Entropy —
  commit the n most confident masked positions per step, judged locally.
* Dynamic baselines (§5, Table 3): **EB** (Ben-Hamu et al., 2025)
  entropy-bounded parallel unmasking; **WINO** (Hong et al., 2025)
  wide-in narrow-out commit-then-revoke.
* Carry-ful builtins (the first strategies to use a decode-steering
  carry): **wino_r** (``core/wino.py``) — WINO revocation with
  cross-step pending-commit state and a per-request revocation budget,
  one forward per step; **extrapolate** (``core/extrapolate.py``) —
  confidence-trajectory extrapolation / local determinism propagation
  (Kong et al., 2025): positions whose confidence trajectory
  extrapolates past a threshold commit early *without* a fresh forward.
* **FDM / FDM-A** (the paper's contribution) register themselves from
  ``core/fdm.py`` / ``core/fdm_a.py``.

Third-party strategies can register via ``register_strategy`` directly or
through the ``repro.strategies`` entry-point group — no edits to ``core/``
required.

All strategies share the same jit-friendly primitive: a per-example top-n
masked commit with fixed shapes (ranking instead of dynamic gather).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DecodeConfig, ModelConfig
from repro.core.confidence import (local_confidence, pallas_enabled,
                                   score_logits)

ModelFn = Callable[[jnp.ndarray], jnp.ndarray]   # tokens (B,L) -> logits

NEG = -1e30


def rank_desc(conf: jnp.ndarray) -> jnp.ndarray:
    """Dense descending rank per row: rank 0 = highest confidence."""
    order = jnp.argsort(-conf, axis=-1)
    return jnp.argsort(order, axis=-1)


@jax.named_scope("commit")
def commit_topn(x: jnp.ndarray, conf: jnp.ndarray, cand: jnp.ndarray,
                eligible: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Commit cand tokens at the top-n eligible positions per example.

    conf (B,L) ranking score; eligible (B,L) bool; n (B,) or scalar int.
    """
    c = jnp.where(eligible, conf, NEG)
    ranks = rank_desc(c)
    n_arr = jnp.asarray(n)
    if n_arr.ndim == 0:
        n_arr = n_arr[None].repeat(x.shape[0], 0)
    commit = eligible & (ranks < n_arr[:, None])
    return jnp.where(commit, cand, x)


# --------------------------------------------------------------------------
# the Strategy protocol
# --------------------------------------------------------------------------

class Strategy:
    """Base class for decoding strategies (see module docstring).

    Subclasses implement ``step`` (and ``fused_step`` when ``step`` needs
    host control flow).  ``active`` marks the current semi-AR block's
    still-masked positions; ``n`` is the caller's nominal commit width.
    """

    name: str = ""
    supports_fused: bool = True      # fused_step is lax.while_loop-safe
    carry_is_observational: bool = False
    # True = the carry only *records* (stats counters like FDM-A's phase
    # histogram) and never changes the decode; safe to drop/reset.  False
    # (default) = the carry steers decoding and must be threaded intact.
    positional_carry: bool = False
    # True = the carry is the 2-tuple ``(positional, global)`` described
    # by ``init_carry_shaped``: the positional part's leaves are
    # column-aligned with the canvas, so the cached path can slice them
    # alongside its live window.  False (default) = the carry is opaque
    # and rides every driver whole.
    trace_confidence_tap: bool = False
    # True = the strategy's FIRST full-canvas model_fn call per step is
    # unconditional, so the tracing adapter (core/tracebuffer.py) may
    # wrap model_fn and capture that call's logits for commit-confidence
    # attribution.  False (default) = the call may sit inside a lax.cond
    # branch (extrapolate's skip) where a tap would leak tracers; the
    # adapter falls back to ``trace_confidence``.

    def forwards_per_step(self, dcfg: DecodeConfig) -> float:
        """Nominal batched-forward count per step (upper bound for
        adaptive strategies); used for budgeting, not accounting — the
        step functions return the exact count."""
        return 1.0

    def init_carry(self, cfg: ModelConfig, dcfg: DecodeConfig):
        """Per-decode strategy state.  Fixed-shape pytree; ``()`` = none."""
        return ()

    def init_carry_shaped(self, cfg: ModelConfig, dcfg: DecodeConfig,
                          batch: int, length: int):
        """Shape-aware carry init: ``(batch, length)`` is the (B, L) of
        the canvas the decode will run on (prompt + generation).

        Strategies with per-position state (``positional_carry = True``)
        override THIS method and must return the 2-tuple
        ``(positional, global)`` where every leaf of ``positional`` has
        leading shape ``(B, L, ...)`` column-aligned with the canvas
        (the cached path slices these to its live window and writes them
        back per block) and ``global`` is any fixed-shape pytree that
        rides every driver whole (budgets, counters).  The default
        delegates to the shape-free ``init_carry``."""
        return self.init_carry(cfg, dcfg)

    def begin_block(self, carry, x, in_block):
        """Traceable block-entry hook: called by every driver (host,
        per-block fused, whole-request fused, cached) right before a
        block's first denoising step.  ``in_block`` is the (L,) bool
        column mask of the new block over ``x``'s columns.  Strategies
        with cross-block state that must not leak into a freshly started
        block (WINO revocation's pending commits — a block that already
        streamed may never be re-opened) reset it here."""
        return carry

    def phase_counts(self, carry) -> Dict[str, int]:
        """Host-side: per-phase step counts extracted from the *final*
        carry, for ``SampleStats.phase_counts``.  Strategies that count
        phases on-device (FDM-A accumulates a ``(4,)`` int32 in its carry)
        override this; the default reports none."""
        return {}

    def carry_stats(self, carry) -> Dict[str, float]:
        """Host-side: observational counters extracted from the *final*
        carry and merged onto same-named ``SampleStats`` fields
        (``revocations``, ``skipped_forwards``).  One ``device_get`` at
        the end of decode — never per step."""
        return {}

    def trace_confidence(self, carry, dcfg: DecodeConfig):
        """Trace-safe (B, L) confidence map read from the POST-step
        carry, for strategies whose commit confidence lives in the carry
        rather than a tappable forward (``trace_confidence_tap = False``
        with cross-step state — extrapolate's trajectory).  ``None``
        (default) = no confidence attribution; the tracing adapter
        records NaN at commits."""
        return None

    def trace_phase(self, carry_before, carry_after):
        """Trace-safe scalar int32 phase id derived from one step's
        carry transition, for phase-switching strategies (FDM-A's
        explore/accel/local_only/balance).  ``None`` (default) = no
        phase attribution (recorded as -1)."""
        return None

    def step(self, rng, carry, x, active, model_fn: ModelFn,
             cfg: ModelConfig, dcfg: DecodeConfig, n) -> Tuple:
        raise NotImplementedError

    def fused_step(self, rng, carry, x, active, model_fn: ModelFn,
                   cfg: ModelConfig, dcfg: DecodeConfig, n) -> Tuple:
        """Trace-safe variant; default assumes ``step`` already is."""
        return self.step(rng, carry, x, active, model_fn, cfg, dcfg, n)

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class StatelessStrategy(Strategy):
    """Adapter lifting a carry-less step function into the protocol.

    ``step_fn(rng, x, active, model_fn, cfg, dcfg, n) -> (x, forwards)``
    is the pre-Decoder signature; ``fused_fn`` (optional) is its
    trace-safe form.
    """

    # every builtin stateless step opens with one unconditional
    # full-canvas model_fn(x) — safe for the tracing adapter to tap
    trace_confidence_tap = True

    def __init__(self, name: str, step_fn: Callable,
                 fused_fn: Optional[Callable] = None,
                 forwards: float = 1.0, supports_fused: bool = True):
        self.name = name
        self._step_fn = step_fn
        self._fused_fn = fused_fn or step_fn
        self._forwards = forwards
        self.supports_fused = supports_fused

    def forwards_per_step(self, dcfg: DecodeConfig) -> float:
        return float(self._forwards)

    def step(self, rng, carry, x, active, model_fn, cfg, dcfg, n):
        new_x, fwd = self._step_fn(rng, x, active, model_fn, cfg, dcfg, n)
        return new_x, carry, fwd

    def fused_step(self, rng, carry, x, active, model_fn, cfg, dcfg, n):
        new_x, fwd = self._fused_fn(rng, x, active, model_fn, cfg, dcfg, n)
        return new_x, carry, fwd


def as_strategy(obj) -> Strategy:
    """Coerce a Strategy, registered name, or legacy step callable."""
    if isinstance(obj, Strategy):
        return obj
    if isinstance(obj, str):
        return resolve_strategy(obj)
    if callable(obj):
        return StatelessStrategy(getattr(obj, "__name__", "anonymous"), obj)
    raise TypeError(f"cannot interpret {obj!r} as a decoding strategy")


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, Strategy] = {}
_BUILTINS_LOADED = False
_ENTRY_POINTS_LOADED = False


def register_strategy(strategy=None, *, name: Optional[str] = None,
                      replace: bool = False):
    """Register a ``Strategy`` (instance or zero-arg class).

    Usable as a decorator::

        @register_strategy
        class MyStrategy(Strategy):
            name = "mine"
            ...

    Third-party packages can also publish strategies under the
    ``repro.strategies`` entry-point group; they are loaded lazily on the
    first unresolved lookup.
    """
    if strategy is None:                       # decorator-with-args form
        return lambda s: register_strategy(s, name=name, replace=replace)
    obj = strategy() if isinstance(strategy, type) else strategy
    if not isinstance(obj, Strategy):
        raise TypeError(f"{strategy!r} is not a Strategy")
    key = name or obj.name
    if not key:
        raise ValueError(f"{obj!r} has no name")
    if key in _REGISTRY and not replace and _REGISTRY[key] is not obj:
        raise ValueError(f"strategy {key!r} already registered "
                         "(pass replace=True to override)")
    _REGISTRY[key] = obj
    return strategy


def unregister_strategy(name: str) -> None:
    _REGISTRY.pop(name, None)


def _ensure_builtins() -> None:
    """Builtins that live in their own modules register at import."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    import repro.core.extrapolate    # noqa: F401  (registers "extrapolate")
    import repro.core.fdm            # noqa: F401  (registers "fdm")
    import repro.core.fdm_a          # noqa: F401  (registers "fdm_a")
    import repro.core.wino           # noqa: F401  (registers "wino_r")


def _load_entry_points() -> None:
    global _ENTRY_POINTS_LOADED
    if _ENTRY_POINTS_LOADED:
        return
    _ENTRY_POINTS_LOADED = True
    try:
        from importlib.metadata import entry_points
        eps = entry_points(group="repro.strategies")
    except Exception:
        return
    for ep in eps:
        try:
            obj = ep.load()
            register_strategy(obj, name=ep.name, replace=False)
        except Exception:
            continue                  # a broken plugin must not kill decode


def resolve_strategy(name: str) -> Strategy:
    """Look up a registered ``Strategy`` object by name."""
    if isinstance(name, Strategy):
        return name
    _ensure_builtins()
    if name not in _REGISTRY:
        _load_entry_points()
    if name not in _REGISTRY:
        raise KeyError(f"unknown strategy {name!r}; "
                       f"have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_strategies() -> Tuple[str, ...]:
    _ensure_builtins()
    _load_entry_points()     # list what resolve_strategy would accept
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# baseline step functions (kept as plain functions; adapters register them)
# --------------------------------------------------------------------------
# legacy signature: step(rng, x, active, model_fn, cfg, dcfg, n) ->
#   (new_x, extra_forwards)

def heuristic_step(metric: str):
    def step(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
             dcfg: DecodeConfig, n) -> Tuple[jnp.ndarray, int]:
        logits = model_fn(x)
        s = score_logits(logits, pallas_enabled(dcfg))
        if metric == "random":
            conf = jax.random.uniform(rng, x.shape)
        else:
            conf = local_confidence(s, metric)
        return commit_topn(x, conf, s.argmax, active, n), 1
    return step


def eb_step(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
            dcfg: DecodeConfig, n) -> Tuple[jnp.ndarray, int]:
    """Entropy-bounded: commit everything with H < bound, at least one."""
    logits = model_fn(x)
    s = score_logits(logits, pallas_enabled(dcfg))
    low_entropy = (-s.neg_entropy) < dcfg.eb_threshold
    conf = jnp.where(active, s.neg_entropy, NEG)
    best = rank_desc(conf) == 0                       # guarantee progress
    commit = active & (low_entropy | best)
    return jnp.where(commit, s.argmax, x), 1


def wino_step(rng, x, active, model_fn: ModelFn, cfg: ModelConfig,
              dcfg: DecodeConfig, n) -> Tuple[jnp.ndarray, int]:
    """Wide-in (commit > τ₁) then narrow-out (revoke < τ₂ on re-score)."""
    logits = model_fn(x)
    s = score_logits(logits, pallas_enabled(dcfg))
    conf = jnp.where(active, s.max_prob, NEG)
    best = rank_desc(conf) == 0
    wide = active & ((s.max_prob > dcfg.wino_tau1) | best)
    x_wide = jnp.where(wide, s.argmax, x)
    # verify: re-score the committed tokens in their new context
    logits2 = model_fn(x_wide)
    logp2 = jax.nn.log_softmax(logits2.astype(jnp.float32), axis=-1)
    p_committed = jnp.exp(jnp.take_along_axis(
        logp2, x_wide[..., None], axis=-1)[..., 0])
    revoke = wide & (p_committed < dcfg.wino_tau2) & ~best
    return jnp.where(revoke, cfg.mask_token_id, x_wide), 2


for _metric in ("random", "probability", "margin", "entropy"):
    register_strategy(StatelessStrategy(_metric, heuristic_step(_metric)))
register_strategy(StatelessStrategy("eb", eb_step))
register_strategy(StatelessStrategy("wino", wino_step, forwards=2.0))
