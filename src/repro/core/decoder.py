"""The first-class decoding API: ``Decoder`` + the cross-call runner cache.

The paper's whole contribution is the *strategy* (FDM / FDM-A vs. the
heuristic and dynamic baselines), so the strategy and the machinery that
drives it are first-class objects here, mirroring the
``DiffusionLLM(model, decoder, …)`` composition of the dInfer line of
work:

* ``Strategy`` (``core/strategies.py``) — carries per-decode state
  (``init_carry``), declares its own fused form, registers by name.
* ``Decoder`` (this module) — owns the semi-AR block loop for BOTH
  execution modes (plain full-sequence re-forward, and frozen-prefix
  cached decoding), the RNG threading, ``SampleStats`` accounting,
  per-block streaming callbacks, and the compiled-runner cache.

``Decoder(params_or_model_fn, cfg, dcfg)``:

* **params mode** (pass a params pytree) — the Decoder builds its own
  forwards.  Compiled runners take ``params`` as a *traced argument*, so
  model weights are never baked into an executable: new params with the
  same structure reuse the compilation, and dropping the last user
  reference to the params actually frees everything.
* **model_fn mode** (pass a callable ``tokens -> logits``) — for
  callers that already own a (jitted) forward.  The runner holds the
  callable only through a weakref, dereferenced at trace time.

KV caching is a first-class axis of the execution surface
(``DecodeConfig.cache_policy`` ∈ ``{none, prefix, dual}``, DESIGN.md
"The KV cache"): ``prefix`` freezes the prompt's K/V and keeps the whole
generation region live; ``dual`` (Fast-dLLM-style) additionally freezes
committed blocks and the masked suffix, recomputing only the active
block.  Both ride the SAME fused drivers as the plain path — the
fixed-shape cache is a traced runner argument threaded through the
``lax.scan`` carry, so one executable per strategy × shape × policy
serves every prompt length, and all three drivers (host loop, per-block
fused, whole-request fused) decode bit-identically per policy.  The
legacy ``generate_cached`` shrinking-window path is subsumed by
``cache_policy="prefix"`` (see the DESIGN.md migration note).

The runner cache (``RunnerCache``) is module-global and *weak*: entries
are keyed on the identity of the params leaves (or the model_fn) and
evicted by a ``weakref.finalize`` when the keying object is collected.
This replaces two seed-era idioms with one mechanism: the seed's
``lru_cache`` over runners (which pinned model_fns/params forever — a
leak for long-lived multi-model serving) and its per-call re-jit of the
cached-path forwards (params pytrees don't hash, so the seed simply
recompiled every call).  Repeat decodes with the same weights now
compile nothing, in every policy; ``decode_cache_info()`` exposes
hit/miss/trace counters so tests and benchmarks can assert exactly that.

Streaming: ``generate`` accepts
``on_block_committed(block_index, lo, hi, x)``, fired after each block
commits (the natural streaming grain of blockwise diffusion decoding —
tokens inside a block finalize together).  ``x`` is the live device
canvas; don't block in the callback.
"""
from __future__ import annotations

import contextlib
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DecodeConfig, ModelConfig
from repro.core.loop import (carry_unwindow, carry_window,
                             drive_block, drive_cached_block,
                             drive_request, drive_request_cached,
                             window_geometry)
from repro.core.masking import fully_masked
from repro.core.strategies import Strategy, resolve_strategy
from repro.core.tracebuffer import DecodeTrace, TracingStrategy, tracing


@dataclass
class SampleStats:
    steps: int = 0
    forward_equivalents: int = 0   # batched-forward count (K-search = K)
    wall_time: float = 0.0
    tokens_generated: int = 0
    phase_counts: Dict[str, float] = field(default_factory=dict)
    # per-phase step counts (FDM-A: explore/accel/local_only/balance),
    # accumulated on device in the strategy carry; ints from Decoder
    # (one flag per batch row per step), per-example averages — possibly
    # fractional, still summing to `steps` — from ServingEngine
    revocations: float = 0.0
    # committed tokens un-committed (re-masked) by a revoking strategy
    # (wino_r); whole-batch total from Decoder, pro-rated per request by
    # ServingEngine.  Each revocation is extra work the step/forward
    # counters already include (the re-decode runs as ordinary steps).
    skipped_forwards: float = 0.0
    # model calls AVOIDED by an extrapolating strategy: steps that
    # committed straight from the carry.  Plain path invariant:
    # steps == forward_equivalents + skipped_forwards (the cached path
    # pro-rates forwards by window size but counts skips raw).
    trace: Optional[DecodeTrace] = None
    # per-step telemetry (dcfg.trace=True only): commit order/confidence,
    # revocations, skips, phases — core/tracebuffer.py.

    @property
    def tps(self) -> float:
        return self.tokens_generated / max(self.wall_time, 1e-9)

    @property
    def tokens_per_forward(self) -> float:
        return self.tokens_generated / max(self.forward_equivalents, 1)

    def as_dict(self) -> Dict[str, Any]:
        """The one stable wire/summary form of a decode's stats — the
        HTTP terminal event, ``ServingEngine.summary()``, and the
        benchmarks all read THIS instead of hand-picking fields (they
        had drifted).  JSON-safe, unrounded — aggregators sum these, so
        precision loss here would show up as drift in their invariants;
        the trace object stays off the wire (it has its own endpoint)."""
        return {
            "steps": int(self.steps),
            "forward_equivalents": float(self.forward_equivalents),
            "wall_time_s": float(self.wall_time),
            "tokens_generated": int(self.tokens_generated),
            "tps": float(self.tps),
            "tokens_per_forward": float(self.tokens_per_forward),
            "revocations": float(self.revocations),
            "skipped_forwards": float(self.skipped_forwards),
            "phase_counts": dict(self.phase_counts),
        }


class BlockEvent(NamedTuple):
    """One committed semi-AR block, as yielded by ``Decoder.generate_blocks``
    (and delivered to ``on_block_committed`` callbacks as positional args).
    ``x`` is the live canvas — the whole ``(B, L)`` token array with the
    block's columns ``lo:hi`` finalized."""
    block: int
    lo: int
    hi: int
    x: Any


class CacheInfo(NamedTuple):
    entries: int     # distinct params/model_fn identities alive
    runners: int     # compiled-runner callables across all entries
    hits: int        # runner lookups served without building
    misses: int      # runner builds (new jit wrapper created)
    traces: int      # actual XLA traces of cached runners (recompiles)


class RunnerCache:
    """Weak, identity-keyed cache of compiled decode runners.

    Key = the identity of the model weights (every params leaf) or of the
    model_fn callable; ``weakref.finalize`` anchors on **every** keying
    object evict the whole entry as soon as *any* of them is collected
    (first finalizer wins).  Anchoring only the first leaf would be a
    correctness bug, not just a leak: the key is a tuple of ``id()``s,
    which are only unique while the objects are alive — if a non-first
    leaf dies (e.g. a partial weight swap) while leaf 0 survives, a
    recycled id could silently collide into a false cache hit.  Values
    never reference the keying objects strongly (params are runner
    *arguments*; model_fns are weakref'd), so eviction genuinely fires —
    unlike an ``lru_cache``, nothing here can pin model weights.
    """

    def __init__(self):
        self._entries: Dict[tuple, Dict[tuple, Any]] = {}
        self._finalizers: Dict[tuple, list] = {}
        self.hits = 0
        self.misses = 0
        self.traces = 0

    @staticmethod
    def key_for(model) -> Tuple[tuple, tuple]:
        """(cache key, weakref anchors) for a params pytree or callable."""
        if callable(model):
            return ("fn", id(model)), (model,)
        leaves = jax.tree.leaves(model)
        if not leaves:
            raise ValueError("params pytree has no array leaves")
        if all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves):
            # abstract params (``lower_blocks`` from shapes alone): a
            # params-mode runner depends only on the shapes, and there is
            # no array to anchor the entry's lifetime to
            return ("abstract", jax.tree.structure(model),
                    tuple((a.shape, a.dtype) for a in leaves)), ()
        return ("params", tuple(map(id, leaves))), tuple(leaves)

    def get(self, key: tuple, anchors: tuple, subkey: tuple,
            builder: Callable[[], Any]) -> Any:
        entry = self._entries.get(key)
        if entry is None:
            entry = self._entries[key] = {}
            self._finalizers[key] = [
                weakref.finalize(a, self._evict, key) for a in anchors]
        runner = entry.get(subkey)
        if runner is None:
            self.misses += 1
            runner = entry[subkey] = builder()
        else:
            self.hits += 1
        return runner

    def _evict(self, key: tuple) -> None:
        self._entries.pop(key, None)
        # detach the surviving finalizers: a stale one firing later could
        # evict a NEW entry that reused the (recycled-id) key tuple
        for fin in self._finalizers.pop(key, ()):
            fin.detach()

    def note_trace(self) -> None:
        """Called from inside runner bodies: the side effect executes only
        while jax is tracing, so this counts real (re)compilations."""
        self.traces += 1

    def info(self) -> CacheInfo:
        return CacheInfo(entries=len(self._entries),
                         runners=sum(len(e) for e in self._entries.values()),
                         hits=self.hits, misses=self.misses,
                         traces=self.traces)

    def reset_stats(self) -> None:
        """Zero the hit/miss/trace counters WITHOUT dropping any cached
        runner — compiled work survives, only the accounting restarts."""
        self.hits = self.misses = self.traces = 0

    def clear(self) -> None:
        for fins in list(self._finalizers.values()):
            for fin in fins:
                fin.detach()
        self._entries.clear()
        self._finalizers.clear()
        self.reset_stats()


_GLOBAL_CACHE = RunnerCache()

# conditioning inputs forward() accepts; generate(**extras) validates
# against this so a typo'd keyword fails at the call site instead of
# surfacing as an opaque trace error (or a bogus model input)
_CONDITIONING_KEYS = frozenset({"enc_embeds", "patch_embeds"})


def decode_cache_info() -> CacheInfo:
    """Counters of the process-wide Decoder runner cache."""
    return _GLOBAL_CACHE.info()


def clear_decode_cache() -> None:
    _GLOBAL_CACHE.clear()


def reset_decode_cache_stats() -> None:
    """Zero the process-wide cache's hit/miss/trace counters, keeping its
    compiled runners.  Compile-count assertions (`traces == N`) should
    call this — or use ``decode_cache_scope`` — first, so they measure
    their own work instead of whatever ran earlier in the process (under
    CI test ordering the module-global counters are otherwise a flake
    source)."""
    _GLOBAL_CACHE.reset_stats()


@contextlib.contextmanager
def decode_cache_scope(cache: Optional[RunnerCache] = None):
    """Swap a fresh (or caller-supplied) ``RunnerCache`` in as the
    process-wide cache for the duration of the ``with`` block.

    Decoders constructed inside the scope — including the ones the
    ServingEngine builds internally — resolve
    against the scoped cache, so its counters see exactly the scope's
    work and its entries drop with the scope (previously cached runners
    reappear after exit, untouched).  Yields the scoped cache.
    """
    global _GLOBAL_CACHE
    prev = _GLOBAL_CACHE
    _GLOBAL_CACHE = cache if cache is not None else RunnerCache()
    try:
        yield _GLOBAL_CACHE
    finally:
        _GLOBAL_CACHE = prev


def _tiling_forward(params, cfg: ModelConfig, extras: Dict[str, Any]):
    """tokens (B', L) -> logits, tiling conditioning inputs (enc_embeds /
    patch_embeds) candidate-major to match a K·B folded batch."""
    from repro.models.model import forward

    def mf(t):
        kw = {}
        for k, v in extras.items():
            reps = t.shape[0] // v.shape[0]
            kw[k] = jnp.tile(v, (reps,) + (1,) * (v.ndim - 1)) \
                if reps > 1 else v
        return forward(params, t, cfg, **kw)[0]

    return mf


def _tile_state(st, reps: int):
    """Replicate a DecodeState candidate-major along its batch axis."""
    if reps == 1:
        return st
    from repro.models.model import DecodeState
    ls = jax.tree.map(
        lambda a: jnp.tile(a, (1, reps) + (1,) * (a.ndim - 2))
        if a.ndim >= 2 else a, st.layer_states)
    eo = None if st.enc_out is None else jnp.tile(st.enc_out, (reps, 1, 1))
    return DecodeState(layer_states=ls, enc_out=eo)


def _cached_model_fn(params, cfg: ModelConfig, batch: int) -> Callable:
    """``(x_win, win_lo, state) -> logits`` for the cached drivers,
    tiling the cache candidate-major when a foreseeing strategy folds
    K candidates into the batch axis."""
    from repro.models.model import forward_cached

    def cf(w, win_lo, st):
        return forward_cached(params, w, win_lo,
                              _tile_state(st, w.shape[0] // batch), cfg)

    return cf


def validate_cache_policy(cfg: ModelConfig, dcfg: DecodeConfig) -> None:
    """Boundary validation for the cache-policy axis: raise ``ValueError``
    if ``cfg`` cannot serve ``dcfg.cache_policy`` (callers at trust
    boundaries — ``ServingEngine.submit`` — map this to a 400).

    The fixed-shape block cache scatters fresh window K/V into full-length
    buffers; recurrent state (ssm/hybrid) is a running reduction and has
    no per-position rows to scatter into, so those archs only support
    ``cache_policy="none"``.
    """
    if dcfg.cache_policy == "none":
        return
    if cfg.arch_type in ("ssm", "hybrid") or cfg.attention == "none":
        raise ValueError(
            f"cache_policy={dcfg.cache_policy!r} requires an "
            f"attention-backed architecture (gqa/mla); "
            f"{cfg.name!r} is arch_type={cfg.arch_type!r} with "
            f"attention={cfg.attention!r} — recurrent state cannot ride "
            f"the fixed-shape block cache")


class Decoder:
    """One composable decode stack: block orchestration for any registered
    ``Strategy``, plain or cached execution, shared compiled-runner cache.

    See the module docstring for the two construction modes.  Typical use::

        dec = Decoder(params, cfg, dcfg)
        tokens, stats = dec.generate(rng, prompt)

        # KV-cached decoding is the same call under a different policy:
        dcfg2 = dataclasses.replace(dcfg, cache_policy="prefix")
        tokens, stats = Decoder(params, cfg, dcfg2).generate(rng, prompt)

    ``Decoder`` objects are cheap: compiled runners live in the shared
    module-level cache keyed on the weights' identity, so constructing a
    fresh ``Decoder`` per request (as the ServingEngine does under
    per-request overrides) still compiles nothing after the first decode.
    """

    def __init__(self, model, cfg: ModelConfig, dcfg: DecodeConfig, *,
                 cache: Optional[RunnerCache] = None):
        self.cfg = cfg
        self.dcfg = dcfg
        self._cache = _GLOBAL_CACHE if cache is None else cache
        if callable(model):
            self._model_fn, self._params = model, None
        else:
            self._model_fn, self._params = None, model
        self._key, self._anchor = RunnerCache.key_for(model)
        # optional telemetry hook ``(block_index, t_start_s, t_end_s)``
        # fired around each KV-cache refresh on the blockwise path (the
        # serving layer turns these into trace spans); None = free
        self.on_cache_refresh: Optional[Callable] = None

    # -- geometry ----------------------------------------------------------
    def _geometry(self) -> Tuple[int, int, int, np.ndarray]:
        """Block layout + the per-block commit-width schedules.

        Returns ``(gen, block_size, num_blocks, schedules)`` where
        ``schedules`` is ``(num_blocks, S)`` int32: row ``b``, entry ``i``
        is the nominal commit width handed to the strategy at step ``i``
        of block ``b`` (the index clamps to the row end in the drivers).

        ``dcfg.steps`` is distributed EXACTLY whenever it is feasible
        (``num_blocks ≤ steps ≤ gen_length``): the per-block step budgets
        spread ``steps`` across blocks with the remainder going to the
        leading blocks, and each block's widths spread ``block_size``
        tokens across its budget likewise (the seed floored both
        divisions, so ``steps=10, num_blocks=4`` quietly ran 8 steps).
        When both divisions are exact this degenerates to the seed's
        constant ``n_per_step`` — bit-identical decodes.  A budget below
        ``num_blocks`` is infeasible (each block takes ≥ 1 step) and
        raises; a budget above ``gen_length`` is a CAP, not a target —
        each step commits ≥ 1 token, so a block's schedule tail is
        unreachable and the decode runs ``gen_length`` steps.

        Net-committed accounting: commit schedules may UN-commit.  A
        revoking strategy (``wino_r``) re-masks tokens, so a block's net
        progress per step can fall below the scheduled width and the
        block legitimately overruns its schedule row.  Rows are therefore
        padded with their FINAL width — never zero — so overrun steps
        (reached only by revocation, since non-revoking width-respecting
        strategies' widths sum exactly to ``block_size``) keep committing
        and the block still terminates inside the ``block_size·4`` safety
        cap; width-ignoring strategies never read ``n`` at all.
        """
        dcfg = self.dcfg
        gen, bs = dcfg.gen_length, dcfg.block_size
        assert gen % bs == 0, (gen, bs)
        num_blocks = gen // bs
        if dcfg.steps < num_blocks:
            raise ValueError(
                f"DecodeConfig.steps={dcfg.steps} is infeasible: semi-AR "
                f"decoding runs at least one step per block and "
                f"gen_length={gen} / block_size={bs} gives {num_blocks} "
                f"blocks — raise steps or shrink the block count")
        base, rem = divmod(dcfg.steps, num_blocks)
        budgets = [base + (1 if b < rem else 0) for b in range(num_blocks)]
        sched = np.zeros((num_blocks, max(budgets)), np.int32)
        for b, spb in enumerate(budgets):
            w, wr = divmod(bs, spb)
            widths = [w + 1] * wr + [w] * (spb - wr)
            # pad with the final width (see docstring: revocation overrun)
            sched[b] = widths + [widths[-1]] * (sched.shape[1] - spb)
        return gen, bs, num_blocks, sched

    # -- runner construction (all cached cross-call) -----------------------
    def _plain_runner(self, strat: Strategy,
                      extras: Optional[Dict[str, Any]] = None
                      ) -> Tuple[Callable, tuple]:
        """Per-block fused runner ``(run, lead)``, called as
        ``run(*lead, x, rng, lo, sched, steps, fwd, carry) -> 5-tuple``
        (``lead`` is the weights and extras in params mode); ``lo``
        (block start) and ``sched`` (per-step commit widths) are traced,
        so all blocks (and all later decodes with the same weights) share
        one executable per shape."""
        cfg, dcfg, cache = self.cfg, self.dcfg, self._cache
        bs = dcfg.block_size
        subkey = ("block", strat, cfg, dcfg)
        if self._model_fn is not None:
            if extras:
                raise ValueError("extras require a params-mode Decoder "
                                 "(a model_fn already owns its "
                                 "conditioning)")
            mf_ref = weakref.ref(self._model_fn)

            def build():
                @jax.jit
                def run(x, rng, lo, sched, steps, fwd, carry):
                    cache.note_trace()
                    mf = mf_ref()       # trace-time only; caller holds it
                    if mf is None:
                        raise RuntimeError("model_fn was garbage-collected")
                    pos = jnp.arange(x.shape[1])
                    in_block = (pos >= lo) & (pos < lo + bs)
                    return drive_block(strat, mf, cfg, dcfg, sched,
                                       x, rng, in_block, steps, fwd, carry)
                return run

            return cache.get(self._key, self._anchor, subkey, build), ()

        def build():
            @jax.jit
            def run(params, ex, x, rng, lo, sched, steps, fwd, carry):
                cache.note_trace()
                pos = jnp.arange(x.shape[1])
                in_block = (pos >= lo) & (pos < lo + bs)
                mf = _tiling_forward(params, cfg, ex)
                return drive_block(strat, mf, cfg, dcfg, sched,
                                   x, rng, in_block, steps, fwd, carry)
            return run

        raw = self._cache.get(self._key, self._anchor, subkey, build)
        return raw, (self._params, dict(extras or {}))

    def _request_runner(self, strat: Strategy, stream: bool,
                        extras: Optional[Dict[str, Any]] = None
                        ) -> Tuple[Callable, Optional[dict]]:
        """Whole-request fused runner: ONE compiled dispatch drives every
        block (``core/loop.py:drive_request``).  Signature
        ``run(x, rng, block_los, schedules, steps, fwd, carry)`` with the
        block offsets and commit schedules traced, so one executable per
        strategy × shape serves every prompt length / step budget of that
        shape.

        Streaming: compiled programs outlive any single ``generate`` call,
        so the per-call ``on_block_committed`` cannot be baked in.  The
        streaming variant (``stream=True``, its own cache subkey) routes
        an ordered ``io_callback`` through a mutable holder dict owned by
        the cached runner; ``generate`` installs the live callback before
        dispatch and clears it after the canvas syncs.  Returns
        ``(run, holder)`` — ``holder`` is ``None`` for the plain variant.
        """
        cfg, dcfg, cache = self.cfg, self.dcfg, self._cache
        subkey = ("request", strat, cfg, dcfg, bool(stream))

        def make_emit(holder):
            def emit(blk, lo, hi, canvas):
                cb = holder.get("cb")
                if cb is not None:
                    cb(int(blk), int(lo), int(hi), canvas)
            return emit

        if self._model_fn is not None:
            if extras:
                raise ValueError("extras require a params-mode Decoder "
                                 "(a model_fn already owns its "
                                 "conditioning)")
            mf_ref = weakref.ref(self._model_fn)

            def build():
                holder = {"cb": None} if stream else None
                emit = make_emit(holder) if stream else None

                @jax.jit
                def run(x, rng, los, scheds, steps, fwd, carry):
                    cache.note_trace()
                    mf = mf_ref()
                    if mf is None:
                        raise RuntimeError("model_fn was garbage-collected")
                    return drive_request(strat, mf, cfg, dcfg, x, rng,
                                         los, scheds, steps, fwd, carry,
                                         emit=emit)
                return run, holder

            return cache.get(self._key, self._anchor, subkey, build)

        def build():
            holder = {"cb": None} if stream else None
            emit = make_emit(holder) if stream else None

            @jax.jit
            def run(params, ex, x, rng, los, scheds, steps, fwd, carry):
                cache.note_trace()
                mf = _tiling_forward(params, cfg, ex)
                return drive_request(strat, mf, cfg, dcfg, x, rng,
                                     los, scheds, steps, fwd, carry,
                                     emit=emit)
            return run, holder

        raw, holder = self._cache.get(self._key, self._anchor, subkey,
                                      build)
        params, ex = self._params, dict(extras or {})
        return (lambda x, rng, los, scheds, steps, fwd, carry:
                raw(params, ex, x, rng, los, scheds, steps, fwd, carry),
                holder)

    def _host_model_fn(self, extras: Optional[Dict[str, Any]]) -> Callable:
        """tokens -> logits for the legacy host step loop."""
        if self._model_fn is not None:
            if extras:
                raise ValueError("extras require a params-mode Decoder")
            return self._model_fn
        cfg, cache = self.cfg, self._cache

        def build():
            @jax.jit
            def fwd(params, ex, t):
                cache.note_trace()
                return _tiling_forward(params, cfg, ex)(t)
            return fwd

        raw = cache.get(self._key, self._anchor, ("fwd", cfg), build)
        params, ex = self._params, dict(extras or {})
        return lambda t: raw(params, ex, t)

    def _refresh_runner(self) -> Tuple[Callable, tuple]:
        """Jitted cache capture ``(refresh, lead)``, called as
        ``refresh(*lead, canvas) -> DecodeState`` — the prefill and
        block-boundary refresh op of the cached path (one full forward
        over the canvas, LM head skipped).  Strategy- and
        dcfg-independent: every policy and strategy on the same weights
        shares one compilation per canvas shape."""
        cfg, cache = self.cfg, self._cache

        def build():
            from repro.models.model import capture_cache

            @jax.jit
            def refresh(params, canvas):
                cache.note_trace()
                return capture_cache(params, canvas, cfg)
            return refresh

        raw = cache.get(self._key, self._anchor, ("refresh", cfg), build)
        return raw, (self._params,)

    def _cached_forward_fn(self) -> Callable:
        """Jitted windowed forward ``(x_win, win_lo, state) -> logits``
        for the host step loop of the cached path."""
        cfg, cache = self.cfg, self._cache

        def build():
            from repro.models.model import forward_cached

            @jax.jit
            def cfwd(params, w, win_lo, st):
                cache.note_trace()
                return forward_cached(params, w, win_lo, st, cfg)
            return cfwd

        raw = cache.get(self._key, self._anchor, ("cached_fwd", cfg),
                        build)
        params = self._params
        return lambda w, win_lo, st: raw(params, w, win_lo, st)

    def _cached_block_runner(self, strat: Strategy
                             ) -> Tuple[Callable, tuple]:
        """Per-block fused runner ``(run, lead)`` for the cached path,
        called as ``run(*lead, x, rng, lo, sched, steps, fwd, carry,
        state)`` over the FULL canvas — window slicing happens inside the
        trace (``drive_cached_block``), with ``lo`` traced, so one executable
        per strategy × shape × policy serves every block of every
        request.  ``state`` is the traced fixed-shape cache from
        ``_refresh_runner`` (never a baked const — ANA103)."""
        cfg, dcfg, cache = self.cfg, self.dcfg, self._cache
        subkey = ("cached_block", strat, cfg, dcfg)

        def build():
            @jax.jit
            def run(params, x, rng, lo, sched, steps, fwd, carry, state):
                cache.note_trace()
                cf = _cached_model_fn(params, cfg, x.shape[0])
                return drive_cached_block(strat, cf, cfg, dcfg, x, rng,
                                          lo, sched, steps, fwd, carry,
                                          state)
            return run

        raw = cache.get(self._key, self._anchor, subkey, build)
        return raw, (self._params,)

    def _cached_request_runner(self, strat: Strategy, stream: bool
                               ) -> Tuple[Callable, Optional[dict]]:
        """Whole-request fused runner for the cached path
        (``drive_request_cached``): prefill, every block's windowed
        ``while_loop`` AND the block-boundary cache refreshes run as one
        compiled dispatch.  Same signature and streaming-holder contract
        as ``_request_runner``."""
        cfg, dcfg, cache = self.cfg, self.dcfg, self._cache
        subkey = ("request_cached", strat, cfg, dcfg, bool(stream))

        def make_emit(holder):
            def emit(blk, lo, hi, canvas):
                cb = holder.get("cb")
                if cb is not None:
                    cb(int(blk), int(lo), int(hi), canvas)
            return emit

        def build():
            holder = {"cb": None} if stream else None
            emit = make_emit(holder) if stream else None

            @jax.jit
            def run(params, x, rng, los, scheds, steps, fwd, carry):
                cache.note_trace()
                from repro.models.model import capture_cache
                cf = _cached_model_fn(params, cfg, x.shape[0])
                return drive_request_cached(
                    strat, cf, lambda cv: capture_cache(params, cv, cfg),
                    cfg, dcfg, x, rng, los, scheds, steps, fwd, carry,
                    emit=emit)
            return run, holder

        raw, holder = self._cache.get(self._key, self._anchor, subkey,
                                      build)
        params = self._params
        return (lambda x, rng, los, scheds, steps, fwd, carry:
                raw(params, x, rng, los, scheds, steps, fwd, carry),
                holder)

    # -- decoding ----------------------------------------------------------
    def generate(self, rng, prompt: jnp.ndarray,
                 strategy: Optional[str] = None,
                 on_block_committed: Optional[Callable] = None,
                 **extras) -> Tuple[jnp.ndarray, SampleStats]:
        """Decode ``gen_length`` tokens after ``prompt`` (B, Lp).
        Returns (tokens (B, Lp+gen), SampleStats).

        ``strategy``: registered name or ``Strategy``; defaults to
        ``dcfg.strategy``.  ``extras`` (params mode only): conditioning
        arrays forwarded to the model (enc_embeds / patch_embeds).
        ``on_block_committed(block_index, lo, hi, x)`` fires after each
        committed block.

        ``dcfg.cache_policy`` selects the execution mode: ``none`` runs a
        full re-forward per step; ``prefix``/``dual`` decode windowed
        steps against the fixed-shape KV cache (params mode only —
        DESIGN.md "The KV cache").  Per policy, three drivers decode
        bit-identical tokens/steps (parity-tested for every registered
        strategy):

        * ``fused_loop ∧ fused_blocks`` (default) — the whole request is
          ONE compiled dispatch (``drive_request`` /
          ``drive_request_cached``, which folds the prefill and every
          block-boundary cache refresh into the same dispatch);
          streaming callbacks fire via ordered ``io_callback``.
        * ``fused_loop ∧ ¬fused_blocks`` — one dispatch per block
          (``drive_block`` / ``drive_cached_block``), callbacks from
          host between blocks.
        * ``¬fused_loop`` — the legacy host step loop, for debugging.

        The two per-block drivers are served by ``generate_blocks`` (the
        block-boundary yield point); this method drains it, forwarding
        events to ``on_block_committed``.
        """
        self._check_extras(extras)
        cfg, dcfg = self.cfg, self.dcfg
        strat = resolve_strategy(strategy or dcfg.strategy)
        if dcfg.trace:
            # the memoized wrapper keeps strategy identity stable across
            # calls, so traced decodes get their own cached runners
            # (per the dcfg-keyed subkeys) without recompiling per call
            # — and trace=off decodes never see the wrapper at all
            strat = tracing(strat)
        cached = dcfg.cache_policy != "none"
        if cached:
            self._check_cached(extras)
        fused = dcfg.fused_loop and strat.supports_fused
        if not (fused and dcfg.fused_blocks):
            blocks = self.generate_blocks(rng, prompt, strategy=strat,
                                          **extras)
            while True:
                try:
                    ev = next(blocks)
                except StopIteration as fin:
                    return fin.value
                if on_block_committed is not None:
                    on_block_committed(ev.block, ev.lo, ev.hi, ev.x)
        b, lp = prompt.shape
        gen, bs, num_blocks, sched = self._geometry()
        x = fully_masked(cfg, prompt, gen)
        carry = strat.init_carry_shaped(cfg, dcfg, b, lp + gen)
        stats = SampleStats(tokens_generated=b * gen)
        t0 = time.perf_counter()

        stream = on_block_committed is not None
        run, holder = self._cached_request_runner(strat, stream) if cached \
            else self._request_runner(strat, stream, extras)
        if holder is not None:
            # the holder is shared through the runner cache by every
            # Decoder on the same weights: refuse to clobber a live
            # callback (concurrent/re-entrant streaming decode) —
            # silent event misdelivery would be far worse
            if holder["cb"] is not None:
                raise RuntimeError(
                    "concurrent streaming decodes with the same "
                    "weights and DecodeConfig are not supported: "
                    "another generate(on_block_committed=...) is "
                    "still in flight for this compiled runner")
            holder["cb"] = on_block_committed
        try:
            los = lp + bs * jnp.arange(num_blocks, dtype=jnp.int32)
            x, rng, steps, fwd, carry = run(
                x, rng, los, jnp.asarray(sched),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
                carry)
            # one sync for the whole decode
            x.block_until_ready()
        finally:
            if holder is not None:
                # output readiness does NOT imply host-callback
                # completion on async backends: drain the ordered
                # io_callbacks before releasing the holder, or the
                # tail events would be dropped (or delivered to the
                # next streaming decode's callback)
                jax.effects_barrier()
                holder["cb"] = None
        stats.steps = int(jax.device_get(steps))
        stats.forward_equivalents = float(jax.device_get(fwd))
        if isinstance(strat, TracingStrategy):
            stats.trace = strat.extract(carry)
        self._merge_carry_stats(stats, strat, carry)
        stats.wall_time = time.perf_counter() - t0
        return x, stats

    def generate_blocks(self, rng, prompt: jnp.ndarray,
                        strategy: Optional[str] = None, **extras):
        """The block-boundary yield point: decode like ``generate`` but at
        the per-block grain, handing control back to the caller after
        every committed block.

        Returns a generator of ``BlockEvent(block, lo, hi, x)``; the
        generator's return value (``StopIteration.value``) is the same
        ``(tokens, stats)`` pair ``generate`` returns.  Between blocks the
        caller may do anything — fan events out to streams, check
        cancellation deadlines, admit new work to other queues — which is
        exactly the scheduling grain of batch-synchronous diffusion
        decoding: a running batch cannot be preempted mid-block, but
        between blocks the host is in full control.  The async serving
        scheduler (``repro.serving.scheduler``) is the primary consumer.

        Drives per-block dispatches (``fused_loop`` chooses the fused
        block runner vs. the legacy host step loop; ``fused_blocks`` does
        not apply — a single whole-request dispatch has no host boundary
        to yield at).  Decodes are bit-identical to ``generate``'s
        (three-driver parity is tested for every registered strategy).
        """
        self._check_extras(extras)
        strat = resolve_strategy(strategy or self.dcfg.strategy)
        if self.dcfg.trace:
            strat = tracing(strat)
        # geometry errors should raise HERE, not at the first next()
        geometry = self._geometry()
        return self._blocks_gen(strat, rng, prompt, geometry, extras)

    def lower_blocks(self, batch: int, prompt_len: int, **extras
                     ) -> Dict[str, jax.stages.Lowered]:
        """Lower, without running, the per-block programs that
        ``generate_blocks`` dispatches for a ``(batch, prompt_len)``
        prompt — what the server runs: ``"block"``, plus ``"refresh"``
        (the cache capture) under a cached policy.  ``compile()`` on each
        gives its ``memory_analysis()`` and text ahead of the first
        request.  The params (and ``extras``) may be
        ``jax.ShapeDtypeStruct``s: lowering needs only their shapes."""
        self._check_extras(extras)
        dcfg = self.dcfg
        if self._params is None:
            raise ValueError("lower_blocks needs a params-mode Decoder")
        strat = resolve_strategy(dcfg.strategy)
        if dcfg.trace:
            strat = tracing(strat)
        if not (dcfg.fused_loop and strat.supports_fused):
            raise ValueError(
                f"lower_blocks lowers the fused block runner, but "
                f"{strat.name!r} with fused_loop={dcfg.fused_loop} "
                f"decodes on the host step loop")
        cached = dcfg.cache_policy != "none"
        if cached:
            self._check_cached(extras)
        gen, _, _, sched = self._geometry()
        prompt = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
        x, steps, fwd, carry = jax.eval_shape(
            lambda p: self._start(strat, p, gen), prompt)
        rng = jax.eval_shape(jax.random.PRNGKey, 0)
        lowered, state = {}, None
        if cached:
            refresh, rlead = self._refresh_runner()
            lowered["refresh"] = refresh.lower(*rlead, x)
            state = jax.eval_shape(refresh, *rlead, x)
        run, lead = self._cached_block_runner(strat) if cached \
            else self._plain_runner(strat, extras)
        args = jax.eval_shape(self._block_args, x, rng, prompt_len,
                              sched[0], steps, fwd, carry, state)
        lowered["block"] = run.lower(*lead, *args)
        return lowered

    def _start(self, strat: Strategy, prompt, gen: int):
        """A decode's first ``(x, steps, fwd, carry)``: the masked canvas,
        the zeroed step and forward counters, the strategy's carry."""
        b, lp = prompt.shape
        return (fully_masked(self.cfg, prompt, gen),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
                strat.init_carry_shaped(self.cfg, self.dcfg, b, lp + gen))

    @staticmethod
    def _block_args(x, rng, lo, sched_row, steps, fwd, carry, state):
        """One fused block dispatch's arguments after the runner's lead;
        ``state`` (the KV cache) is passed only on a cached policy."""
        return (x, rng, jnp.int32(lo), jnp.asarray(sched_row), steps, fwd,
                carry) + (() if state is None else (state,))

    def _blocks_gen(self, strat: Strategy, rng, prompt, geometry, extras):
        cfg, dcfg = self.cfg, self.dcfg
        cached = dcfg.cache_policy != "none"
        if cached:
            self._check_cached(extras)
        b, lp = prompt.shape
        gen, bs, num_blocks, sched = geometry
        total = lp + gen
        x, steps, fwd, carry = self._start(strat, prompt, gen)
        stats = SampleStats(tokens_generated=b * gen)
        t0 = time.perf_counter()
        # cached path: prefill captures the fixed-shape cache (= block 0's
        # refresh); later refreshes run from host at block boundaries.
        # Each capture is one full forward, accounted host-side so all
        # three drivers report the same forward_equivalents.
        refresh, rlead = self._refresh_runner() if cached else (None, ())
        hook = self.on_cache_refresh

        def timed_refresh(canvas, blk):
            if hook is None:
                return refresh(*rlead, canvas)
            # hook installed = serving-layer tracing: the extra sync is
            # paid only then, and the blockwise caller syncs per block
            # anyway (it materializes each block's tokens on host)
            t0r = time.perf_counter()
            st = refresh(*rlead, canvas)
            jax.block_until_ready(st)
            hook(blk, t0r, time.perf_counter())
            return st

        state = timed_refresh(x, 0) if cached else None
        refresh_fwd = 1.0 if cached else 0.0
        fused = dcfg.fused_loop and strat.supports_fused
        if fused:
            run, lead = self._cached_block_runner(strat) if cached \
                else self._plain_runner(strat, extras)
            for blk in range(num_blocks):
                lo = lp + blk * bs
                if cached and blk > 0 and dcfg.cache_refresh == "block":
                    state = timed_refresh(x, blk)
                    refresh_fwd += 1.0
                x, rng, steps, fwd, carry = run(*lead, *self._block_args(
                    x, rng, lo, sched[blk], steps, fwd, carry, state))
                yield BlockEvent(blk, lo, lo + bs, x)
            # one sync for the whole decode: canvas + both stats counters
            x.block_until_ready()
            stats.steps = int(jax.device_get(steps))
            stats.forward_equivalents = float(jax.device_get(fwd)) \
                + refresh_fwd
        else:
            cfwd = self._cached_forward_fn() if cached \
                else self._host_model_fn(extras)
            win, static_lo = window_geometry(dcfg, total) if cached \
                else (total, 0)
            last = sched.shape[1] - 1
            for blk in range(num_blocks):
                lo, hi = lp + blk * bs, lp + (blk + 1) * bs
                if cached and blk > 0 and dcfg.cache_refresh == "block":
                    state = timed_refresh(x, blk)
                    refresh_fwd += 1.0
                # live window: full canvas when uncached; the policy's
                # fixed-width slice when cached (window-relative coords,
                # mirroring drive_cached_block)
                win_lo = 0 if not cached else \
                    (lo if static_lo is None else static_lo)
                x_win = x[:, win_lo:win_lo + win]
                wpos = win_lo + jnp.arange(win)
                in_block = (wpos >= lo) & (wpos < hi)
                scale = win / total if cached else 1.0
                if cached:
                    def mf(w, _st=state, _lo=win_lo):
                        return cfwd(w, jnp.int32(_lo),
                                    _tile_state(_st, w.shape[0] // b))
                    wcarry = carry_window(strat, carry, win_lo, win)
                else:
                    mf, wcarry = cfwd, carry
                wcarry = strat.begin_block(wcarry, x_win, in_block)
                # guard: a strategy always commits ≥1 token/example/step,
                # so a block can never need more than bs·4 steps
                for i in range(bs * 4):
                    active = in_block[None, :] & \
                        (x_win == cfg.mask_token_id)
                    if not bool(jax.device_get(jnp.any(active))):
                        break
                    rng, step_rng = jax.random.split(rng)
                    n = int(sched[blk, min(i, last)])
                    x_win, wcarry, fwd_n = strat.step(
                        step_rng, wcarry, x_win, active, mf, cfg, dcfg, n)
                    stats.steps += 1
                    stats.forward_equivalents += fwd_n * scale
                if cached:
                    x = jax.lax.dynamic_update_slice_in_dim(
                        x, x_win, win_lo, axis=1)
                    carry = carry_unwindow(strat, carry, wcarry, win_lo)
                else:
                    x, carry = x_win, wcarry
                yield BlockEvent(blk, lo, hi, x)
            x.block_until_ready()
            stats.forward_equivalents += refresh_fwd
        if isinstance(strat, TracingStrategy):
            stats.trace = strat.extract(carry)
        self._merge_carry_stats(stats, strat, carry)
        stats.wall_time = time.perf_counter() - t0
        return x, stats

    @staticmethod
    def _check_extras(extras) -> None:
        unknown = set(extras) - _CONDITIONING_KEYS
        if unknown:
            raise TypeError(
                f"got unexpected keyword argument(s) {sorted(unknown)}; "
                f"conditioning extras must be one of "
                f"{sorted(_CONDITIONING_KEYS)}")

    def _check_cached(self, extras) -> None:
        """Entry validation for ``cache_policy != 'none'`` decodes."""
        validate_cache_policy(self.cfg, self.dcfg)
        if self._params is None:
            raise ValueError(
                "cache_policy != 'none' requires a Decoder built from "
                "params (a bare model_fn cannot drive the cache capture "
                "or the windowed forwards)")
        if extras:
            raise ValueError(
                "conditioning extras (enc_embeds / patch_embeds) are not "
                "supported with cache_policy != 'none': the cache capture "
                "runs the text stack only — decode uncached, or drop the "
                "conditioning")

    @staticmethod
    def _merge_carry_stats(stats: SampleStats, strat: Strategy,
                           carry) -> None:
        """Read the strategy's observational counters out of the final
        carry into SampleStats (one host sync per decode, not per step)."""
        pc = strat.phase_counts(carry)
        if pc:
            stats.phase_counts = pc
        for key, val in strat.carry_stats(carry).items():
            if not hasattr(stats, key):
                raise AttributeError(
                    f"strategy {strat.name!r} reported carry stat {key!r} "
                    f"which is not a SampleStats field")
            setattr(stats, key, val)

    # -- introspection -----------------------------------------------------
    def cache_info(self) -> CacheInfo:
        """Counters of the runner cache this Decoder resolves against."""
        return self._cache.info()
