"""Request tracing: per-request span records through the serving stack,
exported as Chrome trace-event JSON (``chrome://tracing`` / Perfetto).

The scheduler records a ``Span`` per lifecycle stage of every request —
``queue_wait`` (submit → batch selection), ``batch_assembly``
(selection + padding), one ``decode_block[i]`` per block-grain executor
round trip (``decode_finish`` for the last, which returns the results),
``fanout[i]`` (the block's events onto the request streams), and
``emit`` (fan-out of the terminal event) — into a ``TraceStore``.  The
engine and the decoder record the host stages inside each round trip,
on the thread that runs them, through the engine's ``on_span`` hook:
``dispatch[i]`` (build block *i*'s arguments and enqueue its program;
``dispatch[0]`` includes the decode's set-up), ``cache_refresh[i]``
(the KV-cache capture, cached policies only), ``device_wait[i]`` (the
host blocked on the block's tokens), ``validate[i]`` (fault filter and
token validation) and ``finish`` (the decode's last sync, the result
copy and the per-request stats).

Every span carries an id and its parent's id: ``decode_block[i]`` is the
parent of the stages that ran inside it and of ``fanout[i]``,
``decode_finish`` of ``finish`` and ``emit``; ``queue_wait``,
``batch_assembly`` and the round trips are roots.

One clock: every span time is ``now()``, the profiler's own host clock
(``CLOCK_REALTIME``, seconds since the epoch; an ``.xplane.pb`` stores
its events relative to the ``profile_start_time`` of its
``Task Environment`` plane, on this clock).  So the spans of different
requests share one timeline, and the spans line up with a device trace.
Each span that wraps live work also opens a
``jax.profiler.TraceAnnotation("repro/<name>")`` over the same interval
on the same thread, so a profiler window shows the program's stages on
its host planes; without an open profiler session that is a no-op check.
Spans recorded after the fact (``queue_wait``) have no annotation.

When the decode ran with ``trace=true`` the request's ``DecodeTrace``
(the on-device TraceBuffer read-back, ``core/tracebuffer.py``) is
attached too, and the export interleaves per-step counter events —
``commits`` (the FINAL commit histogram, so the counter sums exactly to
``tokens_generated`` even under wino_r revocation), ``revocations``,
``skipped``, and the FDM-A phase — across the decode spans' wall-clock
extent.

Export format is the Chrome trace-event JSON object form::

    {"traceEvents": [{"name", "cat", "ph": "X"|"C"|"M",
                      "ts": µs, "dur": µs, "pid", "tid", "args"}, ...],
     "displayTimeUnit": "ms"}

with one process per request (pid = rid) and ``ts`` on the clock above
(no per-request origin), so several requests merge into one viewer
timeline; each span's ``args`` hold its ``id`` and ``parent``.
``GET /v1/trace/{rid}`` serves it; ``tools/trace_view.py`` renders it in
a terminal.

``compile_counter()`` is the process's one ``jax.monitoring`` listener
counting traces, backend compiles and persistent-cache loads; the
scheduler puts the count of each round trip on its span (``compiles``)
and the server exports the totals (``repro_compiles_total{event}``).

Retention mirrors the scheduler's stream retention: traces of finished
requests are kept for the most recent ``retain`` requests, then dropped
FIFO — the scheduler calls ``retire`` from the same choke point that
retires streams and engine bookkeeping.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax

SCHED_TID = 0        # scheduler-lifecycle spans
DEVICE_TID = 1       # on-device step counters

_ids = itertools.count(1)


def now() -> float:
    """Seconds on the profiler's host clock (module docstring)."""
    return time.time()


def new_span_id() -> int:
    return next(_ids)


def annotate(name: str):
    """The profiler mirror of a span: a ``TraceAnnotation`` named
    ``repro/<name>`` on the calling thread."""
    return jax.profiler.TraceAnnotation(f"repro/{name}")


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed interval of a request's life, on ``now()``'s clock."""

    name: str
    cat: str
    start_s: float
    end_s: float
    args: Optional[Dict] = None
    span_id: int = dataclasses.field(default_factory=new_span_id)
    parent_id: Optional[int] = None

    @property
    def dur_s(self) -> float:
        return max(self.end_s - self.start_s, 0.0)


class SpanTimer:
    """``with store.span(rids, "name", "cat"):`` — time the body, mirror
    it in the profiler, and record the span on exit, even when the body
    raises (a failed block dispatch is exactly the span you want to see
    in the trace).  ``span_id`` is known from construction, so work
    inside the body can name this span as its parent."""

    def __init__(self, store: "TraceStore", rids, name: str, cat: str,
                 args: Optional[Dict] = None,
                 parent: Optional[int] = None):
        self.store = store
        self.rids = rids
        self.name = name
        self.cat = cat
        self.args = args
        self.parent = parent
        self.span_id = new_span_id()

    def __enter__(self):
        self._mirror = annotate(self.name)
        self._mirror.__enter__()
        self.start_s = now()
        return self

    def __exit__(self, exc_type, exc, tb):
        span = Span(self.name, self.cat, self.start_s, now(), self.args,
                    self.span_id, self.parent)
        self._mirror.__exit__(None, None, None)
        self.store.add_all(self.rids, span)
        return False


class CompileCounter:
    """Counts the programs JAX traces, compiles, or loads from the
    persistent cache, by event, over the process's life."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/backend_compile_duration":
                  "backend_compile",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_load"}

    def __init__(self):
        self._lock = threading.Lock()
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)

    def on_event(self, event: str, duration: float, **_) -> None:
        kind = self.EVENTS.get(event)
        if kind is not None:
            with self._lock:
                self.counts[kind] += 1

    def total(self) -> int:
        with self._lock:
            return sum(self.counts.values())

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counts)


_counter_lock = threading.Lock()
_counter: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's one compile counter, its listener registered on
    first use."""
    global _counter
    with _counter_lock:
        if _counter is None:
            _counter = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                _counter.on_event)
        return _counter


NO_SPAN = contextlib.nullcontext()


class TraceStore:
    """Per-rid span lists + attached DecodeTraces, bounded FIFO.

    Thread-safe: spans are recorded from the scheduler's event loop AND
    its decode executor thread, while ``/v1/trace`` reads happen on the
    server loop."""

    def __init__(self, retain: int = 256):
        self.retain = max(retain, 1)
        self._lock = threading.Lock()
        self._spans: Dict[int, List[Span]] = {}
        self._traces: Dict[int, object] = {}     # rid -> DecodeTrace
        self._meta: Dict[int, Dict] = {}
        self._retired: Deque[int] = deque()

    def add(self, rid: int, span: Span) -> None:
        with self._lock:
            self._spans.setdefault(rid, []).append(span)

    def add_all(self, rids, span: Span) -> None:
        """One span shared by several requests (a batch's stages)."""
        with self._lock:
            for rid in rids:
                self._spans.setdefault(rid, []).append(span)

    def span(self, rids, name: str, cat: str = "serving",
             args: Optional[Dict] = None,
             parent: Optional[int] = None) -> SpanTimer:
        if isinstance(rids, int):
            rids = (rids,)
        return SpanTimer(self, rids, name, cat, args, parent)

    def attach(self, request_id: int, decode_trace, **meta) -> None:
        """Attach the on-device trace (and wire metadata) on finish.
        ``meta`` keys are free-form (``rid=...`` included — hence the
        positional parameter's longer name)."""
        with self._lock:
            if decode_trace is not None:
                self._traces[request_id] = decode_trace
            self._meta.setdefault(request_id, {}).update(meta)

    def retire(self, rid: int) -> None:
        """The request reached its terminal event; keep its trace for
        the most recent ``retain`` finishers, drop the oldest beyond."""
        with self._lock:
            if rid not in self._spans and rid not in self._traces:
                return
            self._retired.append(rid)
            while len(self._retired) > self.retain:
                old = self._retired.popleft()
                self._spans.pop(old, None)
                self._traces.pop(old, None)
                self._meta.pop(old, None)

    def known(self, rid: int) -> bool:
        with self._lock:
            return rid in self._spans or rid in self._traces

    def chrome(self, rid: int) -> Dict:
        """Chrome trace-event JSON for one request.  ``KeyError`` for an
        unknown (or already-retired) rid."""
        with self._lock:
            if rid not in self._spans and rid not in self._traces:
                raise KeyError(rid)
            spans = list(self._spans.get(rid, ()))
            trace = self._traces.get(rid)
            meta = dict(self._meta.get(rid, ()))
        return chrome_trace(rid, spans, trace, meta)


def _us(t_s: float) -> float:
    return round(t_s * 1e6, 1)


def chrome_trace(rid: int, spans: List[Span], decode_trace=None,
                 meta: Optional[Dict] = None) -> Dict:
    """Assemble the trace-event JSON (module docstring has the shape).

    Device step counters have no host timestamps (the whole point of the
    on-device TraceBuffer is that steps never sync), so the per-step
    counter events are laid out evenly across the wall-clock extent of
    the ``decode_block`` spans — honest about what is known (step order,
    block membership, per-step counts) without inventing per-step times.
    """
    events: List[Dict] = [
        {"name": "process_name", "ph": "M", "pid": rid, "tid": SCHED_TID,
         "args": {"name": f"request {rid}"}},
        {"name": "thread_name", "ph": "M", "pid": rid, "tid": SCHED_TID,
         "args": {"name": "scheduler"}},
    ]
    decode_lo, decode_hi = None, None
    for span in sorted(spans, key=lambda s: s.start_s):
        events.append({
            "name": span.name, "cat": span.cat, "ph": "X",
            "ts": _us(span.start_s),
            "dur": round(span.dur_s * 1e6, 1),
            "pid": rid, "tid": SCHED_TID,
            "args": {**(span.args or {}), "id": span.span_id,
                     "parent": span.parent_id}})
        if span.cat == "decode":
            decode_lo = span.start_s if decode_lo is None \
                else min(decode_lo, span.start_s)
            decode_hi = span.end_s if decode_hi is None \
                else max(decode_hi, span.end_s)

    if decode_trace is not None and decode_trace.steps:
        events.append({"name": "thread_name", "ph": "M", "pid": rid,
                       "tid": DEVICE_TID, "args": {"name": "device steps"}})
        steps = decode_trace.steps
        if decode_lo is None:
            t0 = min((s.start_s for s in spans), default=0.0)
            decode_lo, decode_hi = t0, t0 + steps * 1e-6
        pitch = max((decode_hi - decode_lo) / steps, 1e-9)
        histogram = decode_trace.commit_histogram()
        for i in range(steps):
            ts = _us(decode_lo + i * pitch)
            counters = {"commits": int(histogram[i]),
                        "revocations": int(decode_trace.revocations[i]),
                        "skipped": int(decode_trace.skipped[i])}
            events.append({"name": "commits", "cat": "device", "ph": "C",
                           "ts": ts, "pid": rid, "tid": DEVICE_TID,
                           "args": counters})
            args = {"step": i, "block": int(decode_trace.block[i]),
                    "raw_commits": int(decode_trace.commits[i])}
            if int(decode_trace.phase[i]) >= 0:
                args["phase"] = int(decode_trace.phase[i])
            events.append({"name": f"step {i}", "cat": "device",
                           "ph": "X", "ts": ts,
                           "dur": round(pitch * 1e6, 1),
                           "pid": rid, "tid": DEVICE_TID, "args": args})

    out = {"traceEvents": events, "displayTimeUnit": "ms"}
    if meta:
        out["otherData"] = meta
    return out
