"""Batched serving engine for diffusion-LM decoding.

A miniature vLLM-style front end adapted to the *blockwise* execution model
of masked-diffusion decoding: requests are queued, grouped into fixed-shape
batches, and each batch is decoded through a single ``repro.core.Decoder``
— the first-class decode stack that owns the device-resident fused block
loop, the strategy registry, and the params-keyed cross-call runner cache.
Because that cache is shared and weak, the engine no longer keeps its own
per-sequence-length jit table: repeat batches of any shape reuse the
Decoder's compilations, and dropping an engine (or hot-swapping weights by
building a new one) releases them — the prerequisite for long-lived
multi-model serving.  Diffusion decode is batch-synchronous (every
sequence in the batch advances through the same denoising steps), so the
natural scheduling unit is the *batch*, not the token — continuous
batching applies between blocks, not between tokens.

Scheduling is *prompt-length bucketed*: the queue is scanned into buckets
(prompt length rounded up to ``length_bucket``), shorter prompts in the
chosen batch left-padded with mask tokens — the natural pad for a
masked-diffusion LM, which reads mask as "unknown context" — and the
bucket holding the oldest request is served first.  A single odd-length
prompt at the head therefore cannot strand the rest of the queue.  Padding
stops at the batch's max real length, not the bucket ceiling: mask pads
carry a measurable quality cost (DESIGN.md), so uniform-length workloads
see zero padding.

Per-request decode knobs: ``submit`` accepts ``strategy`` / ``steps`` /
``gen_length`` / ``block_size`` overrides (validated against the strategy
registry and the block geometry at the submission boundary, where a clear
error can still reach the caller).  The effective ``DecodeConfig`` is part
of the bucket key, so only requests decoding identically share a batch —
the ParallelBench observation that dLLM quality/latency trade-offs are
workload-dependent means these knobs must reach the server boundary, and
batching across them would silently decode somebody with somebody else's
settings.

The engine itself is synchronous and single-threaded on purpose; the
batch-selection / batch-decode split (``select_batch`` /
``decode_batch`` / ``decode_batch_blocks``) is what the async scheduler
(``repro.serving.scheduler``) builds its continuous-batching loop on:
selection and queue mutation stay on the event-loop thread, only the
block-grain dispatches run on a worker thread.

Streaming: pass ``on_block_committed(requests, block_index, lo, hi, x)``
to the constructor to observe each committed block of a batch as it lands
(the natural SSE grain for diffusion decoding — tokens inside a block
finalize together).  ``x`` is the live device canvas; don't block in the
callback.

Tracing: ``on_span(requests, name, cat, args)`` (installed by the async
scheduler) returns a context manager that times one host stage of
``decode_batch_blocks`` — ``dispatch[i]``, ``device_wait[i]``,
``validate[i]``, ``finish``, and the decoder's ``cache_refresh[i]`` — on
the thread that runs it (``serving/tracing.py`` has the span tree).
Request times (``submit_time``, ``finish_time``, deadlines) are on the
same clock as the spans, ``tracing.now()``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DecodeConfig, ModelConfig
from repro.core.decoder import (Decoder, SampleStats, validate_block_causal,
                                validate_cache_policy)
from repro.core.strategies import resolve_strategy
from repro.serving.faults import FaultInjector, validate_block_tokens
from repro.serving import tracing


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (Lp,) int32
    result: Optional[np.ndarray] = None
    stats: Optional[SampleStats] = None
    submit_time: float = 0.0
    finish_time: float = 0.0
    dcfg: Optional[DecodeConfig] = None   # effective per-request config
    deadline: Optional[float] = None      # absolute tracing.now() time by
                                          # which decoding must have STARTED
    cancelled: bool = False
    expired: bool = False
    failed: bool = False                  # quarantined / retries exhausted
    pad_cols: int = 0                     # mask pad columns this request got
    retries: int = 0                      # supervision re-queues so far
    group: int = 0                        # bisection cohort (requests only
                                          # co-batch within a group; fresh
                                          # ids keep a failed batch's halves
                                          # from re-merging)

    @property
    def latency(self) -> float:
        return self.finish_time - self.submit_time

    @property
    def status(self) -> str:
        if self.cancelled:
            return "cancelled"
        if self.expired:
            return "expired"
        if self.failed:
            return "error"
        return "done" if self.result is not None else "queued"


@dataclasses.dataclass
class Batch:
    """One schedulable unit: same effective DecodeConfig, same length
    bucket, padded to fixed shape.  Produced by ``select_batch``,
    consumed by ``decode_batch`` / ``decode_batch_blocks``."""
    requests: List[Request]
    prompts: np.ndarray                # (max_batch, Lp) — replicas included
    pads: List[int]                    # per-request mask pad columns
    dcfg: DecodeConfig
    rng: jax.Array


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig, dcfg: DecodeConfig,
                 max_batch: int = 8, seed: int = 0,
                 length_bucket: int = 8,
                 on_block_committed: Optional[Callable] = None,
                 fault_injector: Optional[FaultInjector] = None):
        self.params = params
        self.cfg = cfg
        self.dcfg = dcfg
        self.decoder = Decoder(params, cfg, dcfg)
        self.max_batch = max_batch
        self.length_bucket = max(length_bucket, 1)
        self.on_block_committed = on_block_committed
        # observability hook (installed by the async scheduler):
        # ``(requests, name, cat, args) -> context manager`` around each
        # host stage of ``decode_batch_blocks`` (module docstring)
        self.on_span: Optional[Callable] = None
        self.fault_injector = fault_injector
        self.queue: Deque[Request] = deque()
        self.done: Dict[int, Request] = {}
        self._next_id = 0
        self._next_group = 1
        self._rng = jax.random.PRNGKey(seed)
        self._decoders: Dict[DecodeConfig, Decoder] = {dcfg: self.decoder}

    def set_fault_injector(self,
                           injector: Optional[FaultInjector]) -> None:
        """Attach (or detach) the deterministic fault-injection harness;
        it fires inside ``decode_batch_blocks`` — the supervision
        grain."""
        self.fault_injector = injector

    # -- client API --------------------------------------------------------
    def submit(self, prompt: np.ndarray, *,
               strategy: Optional[str] = None,
               steps: Optional[int] = None,
               gen_length: Optional[int] = None,
               block_size: Optional[int] = None,
               cache_policy: Optional[str] = None,
               trace: Optional[bool] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue a prompt; returns the request id.

        The keyword overrides build this request's effective
        ``DecodeConfig`` (validated HERE — an unknown strategy, an
        infeasible geometry, or a cache policy the model cannot serve
        raises at the submission boundary instead of deep inside a decode
        batch).  Requests only batch with requests sharing the same
        effective config.  ``deadline_s`` bounds QUEUE time: a request
        still queued after it is dropped as expired at the next batch
        selection (admission control for overload — decode work is never
        wasted on a request whose client gave up).
        """
        over = {k: v for k, v in dict(
            strategy=strategy, steps=steps, gen_length=gen_length,
            block_size=block_size, cache_policy=cache_policy,
            trace=trace).items() if v is not None}
        # replace() re-runs DecodeConfig.__post_init__, so an unknown
        # cache_policy raises ValueError right here
        dcfg = dataclasses.replace(self.dcfg, **over) if over else self.dcfg
        resolve_strategy(dcfg.strategy)          # KeyError on unknown name
        validate_cache_policy(self.cfg, dcfg)    # arch can serve the policy?
        for knob in ("gen_length", "block_size", "steps"):
            if getattr(dcfg, knob) < 1:
                raise ValueError(f"{knob}={getattr(dcfg, knob)} must be "
                                 f"a positive integer")
        if dcfg.gen_length % dcfg.block_size:
            raise ValueError(
                f"gen_length={dcfg.gen_length} is not a multiple of "
                f"block_size={dcfg.block_size}")
        num_blocks = dcfg.gen_length // dcfg.block_size
        if dcfg.steps < num_blocks:
            raise ValueError(
                f"steps={dcfg.steps} is infeasible: {num_blocks} blocks "
                f"need at least one step each")
        validate_block_causal(self.cfg, dcfg, len(prompt))
        rid = self._next_id
        self._next_id += 1
        now = tracing.now()
        self.queue.append(Request(
            rid=rid, prompt=np.asarray(prompt), submit_time=now, dcfg=dcfg,
            deadline=None if deadline_s is None else now + deadline_s))
        return rid

    def cancel(self, rid: int) -> bool:
        """Drop a still-queued request.  Returns True if it was removed
        (it lands in ``done`` with ``cancelled=True`` and no result);
        False if it already finished, was never submitted, or is decoding
        right now (a running batch is batch-synchronous and cannot be
        preempted — the result simply arrives and is kept)."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                req.cancelled = True
                req.finish_time = tracing.now()
                self.done[rid] = req
                return True
        return False

    def result(self, rid: int) -> Request:
        return self.done[rid]

    @property
    def queue_depth(self) -> int:
        """Queued (not yet decoding) requests — the backpressure signal."""
        return len(self.queue)

    # -- scheduler ---------------------------------------------------------
    def _bucket_len(self, lp: int) -> int:
        """Round a prompt length up to its bucket ceiling."""
        q = self.length_bucket
        return -(-lp // q) * q

    def _bucket_key(self, req: Request) -> Tuple:
        """Requests batch together iff this matches: same prompt-length
        bucket AND same effective DecodeConfig (frozen → hashable) AND
        same bisection cohort (supervision re-queues a failed batch's
        halves under fresh group ids precisely so they cannot re-merge
        into the batch that just failed).

        ``cache_policy`` appears explicitly even though ``dcfg`` already
        subsumes it: policies decode through DIFFERENT executables with
        different numerics (dual is approximate), so mixed-policy
        co-batching would be a correctness bug, not a batching
        inefficiency — the explicit key component keeps that invariant
        standing if the effective-config keying is ever relaxed."""
        return (self._bucket_len(req.prompt.shape[0]), req.dcfg,
                req.dcfg.cache_policy, req.group)

    # -- supervision hooks (used by the async scheduler) -------------------
    def requeue(self, requests: List[Request],
                fresh_group: bool = False) -> None:
        """Push requests back at the queue FRONT, preserving their order
        (retried work should not queue behind traffic that arrived after
        it).  ``fresh_group=True`` moves the cohort to a new bisection
        group id — the half of a failed batch must never re-co-batch
        with the other half."""
        if fresh_group:
            group = self._next_group
            self._next_group += 1
            for req in requests:
                req.group = group
        for req in reversed(list(requests)):
            req.pad_cols = 0            # re-derived at the next select
            self.queue.appendleft(req)

    def record_failed(self, req: Request,
                      now: Optional[float] = None) -> None:
        """Terminal supervision failure (quarantine / retries exhausted):
        the request lands in ``done`` with no result, visible to
        ``result(rid)`` and excluded from throughput accounting exactly
        like a cancelled one."""
        req.failed = True
        req.finish_time = tracing.now() if now is None else now
        self.done[req.rid] = req

    def adopt(self, old: "ServingEngine") -> None:
        """Carry another engine's in-flight bookkeeping into this one —
        the supervisor's engine-rebuild path: queued requests (their
        effective configs ride along), finished history, and the rid /
        bisection-group counters, so streams and ``result(rid)`` survive
        the swap.  The fault injector and hooks are NOT adopted: the
        rebuilt engine starts with whatever its factory installed."""
        self.queue.extend(old.queue)
        old.queue.clear()
        self.done.update(old.done)
        self._next_id = max(self._next_id, old._next_id)
        self._next_group = max(self._next_group, old._next_group)

    def reap_expired(self, now: Optional[float] = None) -> List[Request]:
        """Drop queued requests whose deadline passed; returns them (also
        recorded in ``done`` with ``expired=True``)."""
        now = tracing.now() if now is None else now
        expired = [r for r in self.queue
                   if r.deadline is not None and now > r.deadline]
        for req in expired:
            self.queue.remove(req)
            req.expired = True
            req.finish_time = now
            self.done[req.rid] = req
        return expired

    def select_batch(self) -> Optional[Batch]:
        """Pop one batch from the queue (no decoding).

        The whole queue is scanned into (prompt-length bucket, effective
        DecodeConfig) groups and the group containing the OLDEST request
        is served (up to max_batch, FIFO within the group) — no
        head-of-line blocking on one odd-length prompt or one exotic
        per-request override.  Prompts shorter than the batch's longest
        are left-padded with the mask token; the pad columns sit outside
        every decode block, so they are never committed, and are sliced
        off the per-request results.

        Callers reap expired requests FIRST (``step`` does; the async
        scheduler does too, emitting terminal events for them) — this
        method deliberately does not, so a request can never slip into
        ``done`` unobserved between a caller's reap and its select.
        """
        if not self.queue:
            return None
        head = self._bucket_key(self.queue[0])
        batch: List[Request] = []
        rest: List[Request] = []
        for r in self.queue:
            if self._bucket_key(r) == head and len(batch) < self.max_batch:
                batch.append(r)
            else:
                rest.append(r)
        self.queue = deque(rest)
        # pad only to the batch's max REAL length (≤ the bucket ceiling):
        # mask pads carry a quality cost — the model reads mask count as a
        # length signal (measured: 8 pads cost 78%→47% EM on the sum
        # testbed) — so uniform-length workloads must see zero padding
        lp = max(r.prompt.shape[0] for r in batch)
        pads = [lp - r.prompt.shape[0] for r in batch]
        for r, p in zip(batch, pads):
            r.pad_cols = p
        prompts = np.stack([
            np.concatenate([np.full((p,), self.cfg.mask_token_id,
                                    r.prompt.dtype), r.prompt])
            if p else r.prompt for r, p in zip(batch, pads)])
        # pad the batch to the bucket size (replicate last prompt)
        pad = self.max_batch - len(batch)
        if pad:
            prompts = np.concatenate(
                [prompts, np.repeat(prompts[-1:], pad, 0)])
        self._rng, rng = jax.random.split(self._rng)
        return Batch(requests=batch, prompts=prompts, pads=pads,
                     dcfg=batch[0].dcfg or self.dcfg, rng=rng)

    def _decoder_for(self, dcfg: DecodeConfig) -> Decoder:
        dec = self._decoders.get(dcfg)
        if dec is None:
            # Decoders are cheap (compiled runners live in the shared
            # weak cache keyed on the weights), but keep a small table so
            # repeat overrides don't even re-key
            if len(self._decoders) > 32:
                self._decoders.clear()
                self._decoders[self.dcfg] = self.decoder
            dec = self._decoders[dcfg] = Decoder(self.params, self.cfg,
                                                 dcfg)
        return dec

    def decode_batch(self, batch: Batch,
                     on_block_committed: Optional[Callable] = None
                     ) -> List[int]:
        """Decode one selected batch to completion (single dispatch when
        the whole-request driver applies).  Returns finished rids."""
        cb = None
        if on_block_committed is not None:
            def cb(blk, lo, hi, x):
                return on_block_committed(batch.requests, blk, lo, hi, x)
        dec = self._decoder_for(batch.dcfg)
        out, stats = dec.generate(batch.rng, jnp.asarray(batch.prompts),
                                  on_block_committed=cb)
        return self._finish_batch(batch, out, stats)

    def decode_batch_blocks(self, batch: Batch) -> Iterator[Tuple]:
        """Decode one selected batch at the BLOCK grain: a generator
        yielding ``(block_index, lo, hi, block_tokens)`` after each
        committed block — ``block_tokens`` is the host-side ``(B, bs)``
        token slice (replica rows included), ready to fan out to
        per-request streams — and returning the finished rids.

        Between yields the caller owns the host (the engine is built on
        ``Decoder.generate_blocks``): the async scheduler runs each
        resumption on a worker thread and uses the gaps to deliver
        events and keep its event loop live.  The engine-level
        ``on_block_committed`` hook fires here too, with the same
        signature as in ``decode_batch``.

        This is also the FAULT BOUNDARY: an attached ``FaultInjector``
        fires here (raised exceptions / simulated OOM / injected stalls
        before a block, NaN-style token corruption after it), and every
        committed block passes the always-on output validator
        (``CorruptOutputError`` on out-of-vocab tokens — the host-side
        signature of non-finite logits).  Failures therefore surface at
        a block boundary of a specific batch, which is the grain the
        supervision layer retries, bisects, and quarantines at.  A
        failed attempt never reaches ``_finish_batch``: results and
        stats only land on success, so a retried batch is
        bit-identical to a fault-free decode.
        """
        inj = self.fault_injector
        bi = inj.begin_batch() if inj is not None else 0
        rids = [r.rid for r in batch.requests]
        dec = self._decoder_for(batch.dcfg)
        hook = self.on_span

        def stage(name, cat="engine", args=None):
            if hook is None:
                return tracing.NO_SPAN
            return hook(batch.requests, name, cat, args)

        # decoders are per-config and the engine decodes one batch at a
        # time, so pointing the decoder hook at this batch's requests is
        # race-free
        dec.on_span = stage if hook is not None else None
        num_blocks = batch.dcfg.gen_length // batch.dcfg.block_size
        blocks = None
        block_index = 0
        while True:
            if inj is not None:
                inj.before_block(bi, rids, block_index)
            name = "finish" if block_index == num_blocks \
                else f"dispatch[{block_index}]"
            with stage(name):
                if blocks is None:
                    blocks = dec.generate_blocks(batch.rng,
                                                 jnp.asarray(batch.prompts))
                try:
                    ev = next(blocks)
                except StopIteration as fin:
                    out, stats = fin.value
                    return self._finish_batch(batch, out, stats)
            with stage(f"device_wait[{block_index}]"):
                tokens = np.asarray(ev.x[:, ev.lo:ev.hi])
            with stage(f"validate[{block_index}]"):
                if inj is not None:
                    tokens = inj.filter_tokens(bi, rids, ev.block, tokens)
                validate_block_tokens(tokens, self.cfg.vocab_size)
            block_index += 1
            if self.on_block_committed is not None:
                self.on_block_committed(batch.requests, ev.block, ev.lo,
                                        ev.hi, ev.x)
            yield (ev.block, ev.lo, ev.hi, tokens)

    def _finish_batch(self, batch: Batch, out, stats: SampleStats
                      ) -> List[int]:
        out = np.asarray(jax.device_get(out))
        now = tracing.now()
        real = len(batch.requests)
        rows = len(batch.prompts)
        for i, req in enumerate(batch.requests):
            req.result = out[i, batch.pads[i]:]
            # per-request stats copy: each request gets its SHARE of the
            # batch's work — tokens (its own gen_length), forwards, and
            # wall time all divided across the real (non-pad-replicated)
            # members, so derived rates (tps, tokens_per_forward) come out
            # consistent: a request's tps equals the batch's aggregate
            # decode throughput, the rate it actually experienced.  The
            # seed pro-rated forwards only, leaving tps wrong by a factor
            # of `real`.  `steps` stays the true batch step count (every
            # request genuinely went through all of them — diffusion
            # decode is batch-synchronous); end-to-end latency lives in
            # Request.latency.
            # phase counts accumulate one flag per BATCH ROW per step —
            # pad replicas included — so normalise by the padded row
            # count: the per-example histogram, which keeps the
            # sum(phase_counts) == steps invariant per request and keeps
            # replica rows from inflating the reported phase work.
            # revocations / skipped_forwards and the expert counters are
            # whole-batch totals like forwards: each real request gets its
            # share
            # the trace (dcfg.trace decodes only) is per-POSITION, not
            # pro-rated: each request gets its own row of the commit
            # maps, pad columns sliced off so commit_step indexes line
            # up with the request's own result coordinates
            req.stats = dataclasses.replace(
                stats,
                tokens_generated=batch.dcfg.gen_length,
                forward_equivalents=stats.forward_equivalents / real,
                wall_time=stats.wall_time / real,
                revocations=stats.revocations / real,
                skipped_forwards=stats.skipped_forwards / real,
                expert_rows=stats.expert_rows / real,
                experts_read=stats.experts_read / real,
                expert_calls=stats.expert_calls / real,
                phase_counts={k: v / rows
                              for k, v in stats.phase_counts.items()},
                trace=stats.trace.slice_rows(i, batch.pads[i])
                if stats.trace is not None else None)
            req.finish_time = now
            self.done[req.rid] = req
        return [r.rid for r in batch.requests]

    def step(self) -> List[int]:
        """Serve one batch from the queue.  Returns finished request ids."""
        self.reap_expired()
        batch = self.select_batch()
        if batch is None:
            return []
        return self.decode_batch(batch, self.on_block_committed)

    def run_until_idle(self) -> None:
        while self.queue:
            self.step()

    # -- metrics -----------------------------------------------------------
    def summary(self) -> Dict[str, float]:
        """Aggregate serving metrics over finished requests.

        Throughput accounting counts REAL requests only: `done` never
        holds pad replicas, and the per-request stats summed here were
        pro-rated across real batch members in `decode_batch`, so
        replicated rows (batches padded to `max_batch`) and mask pad
        columns inflate neither tokens nor forward-equivalents.
        Cancelled/expired requests never decoded, so they are excluded.

        `_finish_batch` may be inserting into `done` from the
        scheduler's worker thread while this runs on the event loop:
        snapshot via ``list(...)`` (one GIL-atomic op) before iterating
        so a mid-scrape batch completion cannot blow up the iteration.
        """
        reqs = [r for r in list(self.done.values())
                if r.stats is not None]
        if not reqs:
            return {}
        lat = [r.latency for r in reqs]
        # one stable stats form: aggregate over as_dict(), the same wire
        # shape the HTTP terminal event and the benchmarks read
        stats = [r.stats.as_dict() for r in reqs]
        toks = sum(s["tokens_generated"] for s in stats)
        fwds = sum(s["forward_equivalents"] for s in stats)
        decode_s = sum(s["wall_time_s"] for s in stats)
        span = max(r.finish_time for r in reqs) - \
            min(r.submit_time for r in reqs)
        return {"requests": len(reqs),
                "mean_latency_s": float(np.mean(lat)),
                "p95_latency_s": float(np.percentile(lat, 95)),
                "throughput_tps": toks / max(span, 1e-9),
                "decode_tps": toks / max(decode_s, 1e-9),
                "forward_equivalents": float(fwds),
                "revocations": float(sum(s["revocations"]
                                         for s in stats)),
                "skipped_forwards": float(sum(s["skipped_forwards"]
                                              for s in stats))}
