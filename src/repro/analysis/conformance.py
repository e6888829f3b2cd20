"""Jaxpr grain: strategy-contract checking by tracing, never executing.

Every registered strategy must hold four contracts that no unit test can
state once-for-all (they quantify over *future* strategies):

  ANA101  the carry pytree (structure, shapes, dtypes) is a fixed-point
          of ``begin_block``, ``fused_step`` and ``step``, and every
          fused driver (``drive_block``'s while_loop, ``drive_request``'s
          scan, and their KV-cached twins under both cache policies)
          traces with it — a carry that grows or re-dtypes breaks the
          ``lax.while_loop`` carry invariant at runtime, on the first
          request that hits the strategy.
  ANA102  the fused jaxprs contain no callback primitives, except the
          one sanctioned *ordered* streaming ``io_callback`` that
          ``drive_request`` itself plants when given ``emit``.
  ANA103  no constant baked into a fused jaxpr exceeds a byte threshold
          (weights must arrive as traced arguments, or every params
          update recompiles and the executable bloats).
  ANA104  re-tracing ``fused_step`` under ``jax.experimental.enable_x64``
          keeps every canvas/carry leaf out of float64 — a Python-float
          constant that silently promotes doubles the FLOPs the day x64
          is enabled.
  ANA105  the step-telemetry contract: wrapping the strategy in
          ``tracing(...)`` (``core/tracebuffer.py``) must preserve every
          contract above — the TraceBuffer rides the carry, so a
          non-fixed-shape write surfaces as an ANA101 break of the
          wrapped strategy — and with trace **off** the raw drivers'
          jaxprs must contain no ``trace_capacity``-sized array at all:
          telemetry that leaks into the trace=off graph would change
          compiled decode for every request that never asked for it.

Everything runs through ``jax.eval_shape`` / ``jax.make_jaxpr`` on a
tiny synthetic harness (a weightless one-hot "model", B=2, 12-column
canvas, two 4-wide blocks), so a full 10-strategy sweep costs traces,
not decodes, and runs in CI without an accelerator.

``step`` (the host variant) is *allowed* to concretize — strategies like
``extrapolate`` and ``fdm_a`` sync on purpose there — so concretization
errors from ``step`` are tolerated; everything else is a finding.

Entry points: ``check_strategy`` (one strategy -> findings),
``assert_conforms`` (raises ``ConformanceError`` — the conftest guard),
``conformance_findings`` (every registered strategy — the CLI).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding, make_finding
from repro.configs.base import DecodeConfig, ModelConfig

DEFAULT_CONST_BYTES = 1 << 18         # 256 KiB: generous for schedules,
                                      # far below any real weight matrix

_TRACE_TOLERATED = (
    "TracerBoolConversionError", "TracerArrayConversionError",
    "TracerIntegerConversionError", "ConcretizationTypeError",
)


class ConformanceError(AssertionError):
    """A registered strategy violates a fused-decode contract."""


def _tiny_setup(strategy_name: str) -> Tuple[ModelConfig, DecodeConfig]:
    cfg = ModelConfig(name="analysis-tiny", arch_type="dense",
                      num_layers=1, d_model=16, num_heads=2,
                      num_kv_heads=2, d_ff=32, vocab_size=31)
    dcfg = DecodeConfig(gen_length=8, block_size=4, steps=4,
                        strategy=strategy_name, k=2, k1=2)
    return cfg, dcfg


def _toy_model_fn(cfg: ModelConfig) -> Callable:
    v = cfg.vocab_size

    def model_fn(x):
        # weightless but rank-correct: peaked logits, any batch size
        # (FDM calls it with the K-candidate batch folded in)
        return jax.nn.one_hot((x + 1) % v, v, dtype=jnp.float32) * 8.0

    return model_fn


def _spec(tree) -> Tuple:
    """Hashable (treedef, leaf shape/dtype list) signature of a pytree."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef, tuple((jnp.shape(l), jnp.result_type(l))
                           for l in leaves))


def _spec_str(spec) -> str:
    treedef, leaves = spec
    shapes = ", ".join(f"{tuple(s)}:{d}" for s, d in leaves)
    return f"{treedef} [{shapes}]"


def _is_jaxpr(obj) -> bool:
    return hasattr(obj, "eqns") or (hasattr(obj, "jaxpr")
                                    and hasattr(obj.jaxpr, "eqns"))


def _iter_eqns(jaxpr):
    """All equations, recursing into control-flow sub-jaxprs."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in vs:
                if _is_jaxpr(sub):
                    yield from _iter_eqns(sub)


def _iter_consts(jaxpr):
    if hasattr(jaxpr, "consts"):
        yield from jaxpr.consts
    for eqn in _iter_eqns(jaxpr):
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in vs:
                if hasattr(sub, "consts"):
                    yield from sub.consts


def _callbacks(jaxpr) -> List:
    return [e for e in _iter_eqns(jaxpr) if "callback" in e.primitive.name]


def _tolerated(err: Exception) -> bool:
    return type(err).__name__ in _TRACE_TOLERATED


def _toy_cached_fns(cfg: ModelConfig) -> Tuple[Callable, Callable]:
    """Weightless stand-ins for the KV-cached model surface: a per-column
    f32 "cache" captured from the canvas, and a windowed forward that
    reads it back (candidate-folded batches included) — enough for the
    cached drivers to trace with real data dependence on the state."""
    v = cfg.vocab_size

    def refresh_fn(canvas):
        return jnp.asarray(canvas % v, jnp.float32)

    def cached_fn(w, win_lo, state):
        bias = jax.lax.dynamic_slice_in_dim(state, win_lo, w.shape[1],
                                            axis=1)
        reps = w.shape[0] // state.shape[0]
        if reps > 1:
            bias = jnp.tile(bias, (reps, 1))
        return jax.nn.one_hot((w + 1) % v, v, dtype=jnp.float32) * 8.0 \
            + bias[..., None] * 1e-3

    return cached_fn, refresh_fn


def check_strategy(strategy, *, batch: int = 2, prompt_len: int = 4,
                   const_bytes: int = DEFAULT_CONST_BYTES,
                   path: Optional[str] = None) -> List[Finding]:
    """Trace one strategy through the fused drivers — plain AND KV-cached
    (both cache policies) — and return findings."""
    import dataclasses

    from repro.core.loop import (drive_block, drive_cached_block,
                                 drive_request, drive_request_cached)
    from repro.core.strategies import as_strategy

    strat = as_strategy(strategy)
    name = strat.name or type(strat).__name__
    where = path or f"strategy:{name}"
    cfg, dcfg = _tiny_setup(name if isinstance(strategy, str) else "fdm")
    model_fn = _toy_model_fn(cfg)
    out: List[Finding] = []

    def finding(rule, msg):
        out.append(make_finding(rule, where, 0, f"[{name}] {msg}"))

    length = prompt_len + dcfg.gen_length
    x0 = jnp.where(jnp.arange(length)[None, :] < prompt_len, 2,
                   cfg.mask_token_id).astype(jnp.int32)
    x0 = jnp.broadcast_to(x0, (batch, length))
    key = jax.random.PRNGKey(0)
    in_block = (jnp.arange(length) >= prompt_len) & (
        jnp.arange(length) < prompt_len + dcfg.block_size)
    active = in_block[None, :] & (x0 == cfg.mask_token_id)
    n = jnp.asarray(1, jnp.int32)
    sched = jnp.full((dcfg.block_size,), 1, jnp.int32)
    steps0 = jnp.asarray(0, jnp.int32)
    fwd0 = jnp.asarray(0.0, jnp.float32)

    try:
        carry0 = strat.init_carry_shaped(cfg, dcfg, batch, length)
    except Exception as e:
        finding("ANA101", f"init_carry_shaped failed: {e}")
        return out
    carry_spec = _spec(carry0)

    # begin_block must return the same carry signature
    try:
        bb = jax.eval_shape(strat.begin_block, carry0, x0, in_block)
        if _spec(bb) != carry_spec:
            finding("ANA101",
                    "begin_block changes the carry signature: "
                    f"{_spec_str(carry_spec)} -> {_spec_str(_spec(bb))}")
    except Exception as e:
        finding("ANA101", f"begin_block does not trace: {e!r}")

    # fused_step / step: carry and canvas fixed-points (static args —
    # model_fn, configs — are closed over; eval_shape abstracts the rest)
    def step_sig(step_fn, label, tolerate_sync):
        def wrapped(k, c, x, a):
            return step_fn(k, c, x, a, model_fn, cfg, dcfg, n)

        try:
            new_x, new_c, _ = jax.eval_shape(wrapped, key, carry0, x0,
                                             active)
        except Exception as e:
            if tolerate_sync and _tolerated(e):
                return                   # host-only step: sanctioned sync
            finding("ANA101", f"{label} does not trace: {e!r}")
            return
        if _spec(new_x) != _spec(x0):
            finding("ANA101", f"{label} changes the canvas signature: "
                    f"{_spec_str(_spec(x0))} -> {_spec_str(_spec(new_x))}")
        if _spec(new_c) != carry_spec:
            finding("ANA101", f"{label} is not a carry fixed-point: "
                    f"{_spec_str(carry_spec)} -> "
                    f"{_spec_str(_spec(new_c))}")

    step_sig(strat.fused_step, "fused_step", tolerate_sync=False)
    step_sig(strat.step, "step", tolerate_sync=True)
    if out:
        return out          # drivers would only re-report the same break

    # both fused drivers must trace with the carry riding them, and their
    # jaxprs must be free of callbacks / giant consts
    def block_fn(x, k, s, f, c):
        return drive_block(strat, model_fn, cfg, dcfg, sched, x, k,
                           in_block, s, f, c)

    block_los = jnp.asarray([prompt_len, prompt_len + dcfg.block_size],
                            jnp.int32)
    schedules = jnp.broadcast_to(sched, (2, sched.shape[0]))

    def request_fn(x, k, s, f, c):
        return drive_request(strat, model_fn, cfg, dcfg, x, k, block_los,
                             schedules, s, f, c)

    def request_emit_fn(x, k, s, f, c):
        return drive_request(strat, model_fn, cfg, dcfg, x, k, block_los,
                             schedules, s, f, c,
                             emit=lambda blk, lo, hi, canvas: None)

    def check_jaxpr(label, fn, args, emit_ok):
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:
            finding("ANA101", f"{label} does not trace with this "
                    f"strategy's carry: {e!r}")
            return
        for eqn in _callbacks(jaxpr):
            prim = eqn.primitive.name
            if (emit_ok and prim == "io_callback"
                    and eqn.params.get("ordered")):
                continue               # the sanctioned streaming callback
            finding("ANA102", f"{label} jaxpr contains {prim} "
                    "(only the ordered streaming io_callback is "
                    "sanctioned in fused decode)")
        for const in _iter_consts(jaxpr):
            nbytes = getattr(const, "nbytes", 0)
            if nbytes and nbytes > const_bytes:
                finding("ANA103", f"{label} jaxpr bakes a "
                        f"{jnp.shape(const)} constant ({nbytes} B > "
                        f"{const_bytes} B) — pass weights as traced "
                        "arguments, not closure captures")

    plain_args = (x0, key, steps0, fwd0, carry0)
    check_jaxpr("drive_block", block_fn, plain_args, False)
    check_jaxpr("drive_request", request_fn, plain_args, False)
    check_jaxpr("drive_request[emit]", request_emit_fn, plain_args, True)

    # the cached fused drivers hold the same contracts per policy: the
    # carry AND the fixed-shape cache state ride the trace as arguments
    # (a baked cache would be an ANA103 finding), and the only callback
    # is still the sanctioned ordered streaming one
    cached_fn, refresh_fn = _toy_cached_fns(cfg)
    state0 = refresh_fn(x0)
    lo0 = jnp.asarray(prompt_len, jnp.int32)
    for policy in ("prefix", "dual"):
        dc = dataclasses.replace(dcfg, cache_policy=policy)

        def cblock_fn(x, k, lo, s, f, c, st, _dc=dc):
            return drive_cached_block(strat, cached_fn, cfg, _dc, x, k,
                                      lo, sched, s, f, c, st)

        def crequest_fn(x, k, s, f, c, _dc=dc):
            return drive_request_cached(
                strat, cached_fn, refresh_fn, cfg, _dc, x, k, block_los,
                schedules, s, f, c,
                emit=lambda blk, lo, hi, canvas: None)

        check_jaxpr(f"drive_cached_block[{policy}]", cblock_fn,
                    (x0, key, lo0, steps0, fwd0, carry0, state0), False)
        check_jaxpr(f"drive_request_cached[{policy},emit]", crequest_fn,
                    plain_args, True)

    # x64 probe: same 32-bit inputs, x64 enabled — promotion to float64
    # means a float constant somewhere isn't weakly typed
    try:
        def x64_probe(k, c, x, a):
            return strat.fused_step(k, c, x, a, model_fn, cfg, dcfg, n)

        with jax.enable_x64(True):
            new_x, new_c, _ = jax.eval_shape(x64_probe, key, carry0, x0,
                                             active)
            # inspect INSIDE the context: result_type canonicalizes f64
            # back to f32 once x64 is off again, hiding the promotion
            bad = [(jnp.shape(l), str(jnp.result_type(l)))
                   for l in jax.tree.leaves((new_x, new_c))
                   if jnp.result_type(l) == jnp.float64]
        if bad:
            finding("ANA104", "fused_step promotes to float64 under "
                    f"enable_x64 (leaves {bad}) — use explicit 32-bit "
                    "dtypes or weak Python scalars")
    except Exception as e:
        if not _tolerated(e):
            finding("ANA104", f"x64 probe failed to trace: {e!r}")

    return out


def check_trace_telemetry(strategy_name: str, *,
                          const_bytes: int = DEFAULT_CONST_BYTES
                          ) -> List[Finding]:
    """ANA105, two directions per registered strategy:

    * trace **on**: ``tracing(strategy)`` must hold every fused-decode
      contract itself — its carry carries the TraceBuffer, so the
      ANA101 fixed-point check *is* the proof that telemetry writes are
      fixed-shape, and ANA102/103/104 prove the wrapper adds no
      callbacks, baked constants, or f64 promotion.
    * trace **off**: the raw drivers' jaxprs must be entirely free of
      ``trace_capacity(dcfg)``-sized arrays — the buffer must be
      unreachable from the fused roots unless the wrapper was applied.
    """
    from repro.core.loop import drive_block, drive_request
    from repro.core.strategies import as_strategy
    from repro.core.tracebuffer import trace_capacity, tracing

    strat = as_strategy(strategy_name)
    where = f"strategy:{strategy_name}"
    out: List[Finding] = []

    wrapped = tracing(strat)
    for f in check_strategy(wrapped, const_bytes=const_bytes, path=where):
        out.append(make_finding(
            "ANA105", where, 0,
            f"tracing({strategy_name}) breaks {f.rule}: {f.message}"))

    cfg, dcfg = _tiny_setup(strategy_name)
    cap = trace_capacity(dcfg)
    model_fn = _toy_model_fn(cfg)
    batch, prompt_len = 2, 4
    length = prompt_len + dcfg.gen_length
    x0 = jnp.where(jnp.arange(length)[None, :] < prompt_len, 2,
                   cfg.mask_token_id).astype(jnp.int32)
    x0 = jnp.broadcast_to(x0, (batch, length))
    key = jax.random.PRNGKey(0)
    in_block = (jnp.arange(length) >= prompt_len) & (
        jnp.arange(length) < prompt_len + dcfg.block_size)
    sched = jnp.full((dcfg.block_size,), 1, jnp.int32)
    block_los = jnp.asarray([prompt_len, prompt_len + dcfg.block_size],
                            jnp.int32)
    schedules = jnp.broadcast_to(sched, (2, sched.shape[0]))
    steps0 = jnp.asarray(0, jnp.int32)
    fwd0 = jnp.asarray(0.0, jnp.float32)
    try:
        carry0 = strat.init_carry_shaped(cfg, dcfg, batch, length)
    except Exception:
        return out          # the base sweep already reports ANA101

    def scan(label, fn, args):
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception:
            return          # ditto: tracing failures are ANA101's job
        hits = set()
        for eqn in _iter_eqns(jaxpr):
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = tuple(getattr(getattr(v, "aval", None),
                                      "shape", ()) or ())
                if cap in shape:
                    hits.add(shape)
        if hits:
            out.append(make_finding(
                "ANA105", where, 0,
                f"[{strategy_name}] {label} (trace=off) jaxpr contains "
                f"trace_capacity({cap})-sized arrays {sorted(hits)} — "
                "the TraceBuffer must be unreachable unless "
                "dcfg.trace wrapped the strategy"))

    plain_args = (x0, key, steps0, fwd0, carry0)
    scan("drive_block",
         lambda x, k, s, f, c: drive_block(strat, model_fn, cfg, dcfg,
                                           sched, x, k, in_block, s, f,
                                           c),
         plain_args)
    scan("drive_request",
         lambda x, k, s, f, c: drive_request(strat, model_fn, cfg, dcfg,
                                             x, k, block_los, schedules,
                                             s, f, c),
         plain_args)
    return out


def assert_conforms(strategy) -> None:
    """Raise ``ConformanceError`` listing every violated contract."""
    problems = check_strategy(strategy)
    if problems:
        lines = "\n".join(f"  {f.rule}: {f.message}" for f in problems)
        raise ConformanceError(
            f"strategy fails fused-decode conformance:\n{lines}")


def conformance_findings(names: Optional[Sequence[str]] = None,
                         const_bytes: int = DEFAULT_CONST_BYTES
                         ) -> List[Finding]:
    """Check every registered strategy (the CLI's jaxpr grain): the base
    fused-decode contracts plus the ANA105 telemetry contract."""
    from repro.core.strategies import available_strategies
    out: List[Finding] = []
    for name in names if names is not None else available_strategies():
        out.extend(check_strategy(name, const_bytes=const_bytes))
        out.extend(check_trace_telemetry(name, const_bytes=const_bytes))
    return out
