"""Client-side load: drives the server over HTTP/SSE and timestamps
every request from the client's side (``time.perf_counter``, the clock
the server's spans use).

Two shapes of load, as the mix says:

* backlog (an offline job): ``backlog`` client threads each keep one
  request in flight, so the server's queue holds ``backlog - max_batch``
  requests while a batch decodes.  The window opens at the first block
  any request receives and lasts ``seconds``; then queued requests are
  cancelled and the decoding batch is let finish.
* poisson (independent users): a dispatcher sends each request at its
  due time, whether or not earlier ones have finished; a request is
  timed from when it was due.  The window is the span of due times; the
  run waits for every request due in it (at most ``drain_s`` past the
  close).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import numpy as np

from bench import traffic as traffic_lib


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""
    index: int
    prompt: np.ndarray
    due: float = float("nan")          # perf_counter when it was due
    sent: float = float("nan")         # perf_counter just before POST
    rid: int = -1
    blocks: List[tuple] = dataclasses.field(default_factory=list)
                                       # (perf_counter, block index)
    final_t: float = float("nan")
    status: str = ""
    tokens: Optional[list] = None
    stats: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    error: str = ""

    @property
    def ok(self) -> bool:
        return self.status == "ok"


def _serve_one(client, model: str, rec: Record, decode: dict,
               want_spans: bool, on_block: Callable[[], None]) -> None:
    from repro.serving.client import ServerError
    try:
        rec.sent = time.perf_counter()
        sub = client.generate(rec.prompt.tolist(), model=model, wait=False,
                              **decode)
        rec.rid = sub["rid"]
        for name, event in client.stream(rec.rid, model=model):
            t = time.perf_counter()
            if event.get("final"):
                rec.final_t = t
                rec.status = event.get("status", "")
                rec.tokens = event.get("tokens")
                rec.stats = event.get("stats") or {}
                rec.error = event.get("error", "")
            elif name == "block":
                rec.blocks.append((t, event["block"]))
                on_block()
        if want_spans and rec.ok:
            rec.spans = client.trace(rec.rid, model=model)["traceEvents"]
    except (ServerError, OSError, ValueError, KeyError) as e:
        rec.final_t = time.perf_counter()
        rec.status = rec.status or "client_error"
        rec.error = f"{type(e).__name__}: {e}"


def _decode_args(mix: dict) -> dict:
    keep = ("strategy", "steps", "gen_length", "block_size", "cache_policy")
    return {k: mix["decode"][k] for k in keep}


class Load:
    """Runs one window of the mix against a started server."""

    def __init__(self, host: str, port: int, model: str, mix: dict,
                 seed: int, mask_id: int, want_spans: bool = False):
        from repro.serving.client import ServingClient
        self.mix, self.seed, self.mask_id = mix, seed, mask_id
        self.model = model
        self.want_spans = want_spans
        self.decode = _decode_args(mix)
        self._client = lambda: ServingClient(host, port, timeout=300.0,
                                             max_retries=0)
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._first_block = threading.Event()
        self.t0 = self.t1 = float("nan")
        self.lateness_s: List[float] = []

    def _on_block(self) -> None:
        self._first_block.set()

    def run(self, seconds: float, on_open: Callable[[float], None],
            drain_s: float = 60.0) -> None:
        """Offer the window's load; ``on_open(t0)`` is called, and must
        return at once, when the window opens."""
        if self.mix["arrivals"] == "backlog":
            self._run_backlog(seconds, on_open, drain_s)
        else:
            self._run_poisson(seconds, on_open, drain_s)

    # -- offline job -------------------------------------------------------
    def _run_backlog(self, seconds, on_open, drain_s):
        n_workers = int(self.mix["backlog"])
        # enough lengths for any window: one request per worker per block
        plan = traffic_lib.length_plan(self.mix, 4096)
        stop = threading.Event()
        counter = iter(range(len(plan)))

        def worker():
            client = self._client()
            while not stop.is_set():
                with self._lock:
                    i = next(counter)
                    rec = Record(i, traffic_lib.prompt(
                        self.mix, self.seed, i, plan[i], self.mask_id))
                    self.records.append(rec)
                rec.due = time.perf_counter()
                _serve_one(client, self.model, rec, self.decode,
                           self.want_spans, self._on_block)

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"bench-client-{j}")
                   for j in range(n_workers)]
        for t in threads:
            t.start()
        if not self._first_block.wait(timeout=900.0):
            stop.set()
            raise RuntimeError("no block came back within 900 s")
        self.t0 = time.perf_counter()
        self.t1 = self.t0 + seconds
        on_open(self.t0)
        time.sleep(max(self.t1 - time.perf_counter(), 0.0))
        stop.set()
        client = self._client()
        for _ in range(2):      # a second pass catches in-flight POSTs
            with self._lock:
                waiting = [r for r in self.records
                           if r.rid >= 0 and not r.blocks and not r.status]
            for rec in waiting:
                try:
                    client.cancel(rec.rid, model=self.model)
                except OSError:
                    pass
            time.sleep(0.5)
        for t in threads:
            t.join(timeout=drain_s + 300.0)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client thread did not end")

    # -- independent users ------------------------------------------------
    def _run_poisson(self, seconds, on_open, drain_s):
        plan = traffic_lib.schedule(self.mix, seconds, self.seed,
                                    self.mask_id)
        self.records = [Record(i, p) for i, (_, p) in enumerate(plan)]
        local = threading.local()

        def send(rec):
            if not hasattr(local, "client"):
                local.client = self._client()
            _serve_one(local.client, self.model, rec, self.decode,
                       self.want_spans, self._on_block)

        with ThreadPoolExecutor(max_workers=64,
                                thread_name_prefix="bench-client") as pool:
            self.t0 = time.perf_counter() + 0.05
            self.t1 = self.t0 + seconds
            futures = []
            for (due, _), rec in zip(plan, self.records):
                rec.due = self.t0 + due
                wait = rec.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.lateness_s.append(time.perf_counter() - rec.due)
                futures.append(pool.submit(send, rec))
                if len(futures) == 1:
                    on_open(self.t0)
            deadline = self.t1 + drain_s
            for f in futures:
                f.result(timeout=max(deadline - time.perf_counter(), 1.0))
