"""End-to-end decode observability: the metrics registry (Prometheus
text exposition), on-device step telemetry (``dcfg.trace`` →
``SampleStats.trace``), request tracing through the serving stack
(``/v1/trace/{rid}`` Chrome trace-event JSON), and the ANA105 telemetry
contract."""
import asyncio
import dataclasses
import glob
import importlib.util
import io
import json
import os
import re
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs import (DecodeConfig, RouterConfig, ServerConfig,
                           get_config)
from repro.core import Decoder, decode_cache_scope, decode_cache_info
from repro.core.decoder import SampleStats
from repro.core.tracebuffer import DecodeTrace, trace_capacity, tracing
from repro.models.model import init_model
from repro.serving import (AsyncScheduler, ModelRouter, ServerError,
                           ServerThread, ServingClient, ServingEngine)
from repro.serving.metrics import (CONTENT_TYPE, Family, MetricsRegistry,
                                   escape_label_value, format_value)
from repro.serving.tracing import (CompileCounter, Span, TraceStore,
                                   chrome_trace, compile_counter)

CFG = get_config("llada-8b").reduced()
DCFG = DecodeConfig(gen_length=16, block_size=8, steps=16,
                    strategy="probability")
PROMPT = [3, 5, 2, 7, 4, 6]


@pytest.fixture(scope="module")
def params():
    return init_model(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_renders_help_type_and_bare_ints():
    reg = MetricsRegistry()
    reg.gauge("g", "a gauge").set(3)
    reg.counter("c_total", "a counter", ("model",)) \
        .labels(model="tiny").inc(2)
    text = reg.render()
    assert "# HELP g a gauge\n# TYPE g gauge\ng 3\n" in text
    assert '# TYPE c_total counter\nc_total{model="tiny"} 2\n' in text
    assert text.endswith("\n")


def test_registry_label_escaping_round_trip():
    reg = MetricsRegistry()
    nasty = 'ti"ny\\mod\nel'
    reg.gauge("g", "h", ("model",)).labels(model=nasty).set(1)
    line = [l for l in reg.render().splitlines()
            if not l.startswith("#")][0]
    assert line == 'g{model="ti\\"ny\\\\mod\\nel"} 1'
    assert escape_label_value(nasty) in line


def test_histogram_buckets_cumulative_with_inf_sum_count():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "h", ("model",),
                      buckets=(0.1, 1.0))
    child = h.labels(model="a")
    for v in (0.05, 0.5, 2.0):
        child.observe(v)
    lines = reg.render().splitlines()
    assert 'lat_seconds_bucket{model="a",le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{model="a",le="1"} 2' in lines
    assert 'lat_seconds_bucket{model="a",le="+Inf"} 3' in lines
    assert 'lat_seconds_sum{model="a"} 2.55' in lines
    assert 'lat_seconds_count{model="a"} 3' in lines


def test_registry_instrument_misuse_raises():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "h")
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("c_total", "h") is c       # idempotent re-get
    with pytest.raises(ValueError):
        reg.gauge("c_total", "h")                 # type conflict
    with pytest.raises(ValueError):
        reg.counter("c_total", "h", ("model",))   # label conflict
    with pytest.raises(ValueError):
        c.labels(model="x")                       # undeclared label


def test_collector_families_render_live_snapshots():
    reg = MetricsRegistry()
    state = {"v": 1}
    reg.register_collector(lambda: [
        Family("live", "gauge", "snapshot", [({}, state["v"])])])
    assert "live 1" in reg.render()
    state["v"] = 7
    assert "live 7" in reg.render()


def test_format_value_spellings():
    assert format_value(True) == "1"
    assert format_value(3) == "3"
    assert format_value(3.0) == "3"
    assert format_value(float("inf")) == "+Inf"
    assert format_value(float("nan")) == "NaN"
    assert format_value(2.55) == "2.55"


# --------------------------------------------------------------------------
# SampleStats.as_dict — the one stable stats shape
# --------------------------------------------------------------------------

def test_as_dict_is_unrounded_and_json_safe():
    stats = SampleStats(steps=16, forward_equivalents=16 / 3,
                        wall_time=0.123456789, tokens_generated=16,
                        revocations=1.0, skipped_forwards=2.0,
                        phase_counts={"explore": 4.0})
    d = stats.as_dict()
    assert d["forward_equivalents"] == pytest.approx(16 / 3, rel=1e-12)
    assert d["wall_time_s"] == pytest.approx(0.123456789, rel=1e-12)
    assert d["tps"] == pytest.approx(stats.tps, rel=1e-12)
    assert d["tokens_per_forward"] == pytest.approx(
        stats.tokens_per_forward, rel=1e-12)
    json.dumps(d)                                 # trace stays off-wire
    assert "trace" not in d


# --------------------------------------------------------------------------
# on-device step telemetry: parity, isolation, histogram invariant
# --------------------------------------------------------------------------

def _decode(params, *, trace, fused_loop=True, fused_blocks=True,
            strategy="probability"):
    dcfg = dataclasses.replace(DCFG, trace=trace, fused_loop=fused_loop,
                               fused_blocks=fused_blocks,
                               strategy=strategy)
    dec = Decoder(params, CFG, dcfg)
    out, stats = dec.generate(jax.random.PRNGKey(7),
                              np.asarray(PROMPT, np.int32)[None])
    return np.asarray(out), stats


def test_trace_off_is_bit_identical_and_recompile_free(params):
    with decode_cache_scope():
        off, s_off = _decode(params, trace=False)
        base = decode_cache_info()
        on, s_on = _decode(params, trace=True)
        off2, _ = _decode(params, trace=False)
        after = decode_cache_info()
    np.testing.assert_array_equal(off, on)        # telemetry is passive
    np.testing.assert_array_equal(off, off2)
    assert s_off.trace is None and s_on.trace is not None
    # the traced decode uses its own runner; the untraced repeat re-hits
    # the original — trace=on never invalidates the trace=off cache
    assert after.hits > base.hits


@pytest.mark.parametrize("fused_loop,fused_blocks",
                         [(True, True), (True, False), (False, False)])
def test_trace_parity_across_drivers(params, fused_loop, fused_blocks):
    ref = _decode(params, trace=True)[1].trace
    trace = _decode(params, trace=True, fused_loop=fused_loop,
                    fused_blocks=fused_blocks)[1].trace
    np.testing.assert_array_equal(ref.commit_step, trace.commit_step)
    np.testing.assert_array_equal(ref.commits, trace.commits)
    np.testing.assert_array_equal(ref.block, trace.block)
    np.testing.assert_array_equal(ref.skipped, trace.skipped)


@pytest.mark.parametrize("strategy", ["probability", "wino_r"])
def test_commit_histogram_sums_to_tokens_generated(params, strategy):
    """Under revocation (wino_r) raw per-step commits overcount; the
    FINAL-commit histogram still sums exactly to tokens_generated."""
    out, stats = _decode(params, trace=True, strategy=strategy)
    trace = stats.trace
    hist = trace.commit_histogram()
    assert hist.sum() == stats.tokens_generated
    assert hist.shape == (trace.steps,)
    assert trace.steps <= trace_capacity(DCFG)
    # committed positions are exactly the generated region
    assert (trace.commit_step >= 0).sum() == stats.tokens_generated


def test_tracing_wrapper_memoized_and_idempotent():
    from repro.core.strategies import as_strategy
    from repro.core.tracebuffer import TracingStrategy
    inner = as_strategy("probability")
    wrapped = tracing(inner)
    assert tracing(inner) is wrapped      # identity-stable: runner cache
    assert tracing(wrapped) is wrapped    # idempotent, never double-wraps
    with pytest.raises(TypeError):
        TracingStrategy(wrapped)


# --------------------------------------------------------------------------
# TraceStore / chrome_trace
# --------------------------------------------------------------------------

def _fake_decode_trace(steps=4, length=8):
    commit_step = np.arange(length).reshape(1, -1) % steps
    return DecodeTrace(
        commit_step=commit_step.astype(np.int32),
        commit_conf=np.ones((1, length), np.float32),
        commits=np.full((steps,), length // steps, np.int32),
        revocations=np.zeros((steps,), np.int32),
        skipped=np.zeros((steps,), bool),
        phase=np.full((steps,), -1, np.int32),
        block=np.zeros((steps,), np.int32))


def test_trace_store_retention_fifo():
    store = TraceStore(retain=2)
    for rid in range(4):
        store.add(rid, Span("queue_wait", "serving", 0.0, 1.0))
        store.retire(rid)
    assert not store.known(0) and not store.known(1)
    assert store.known(2) and store.known(3)
    with pytest.raises(KeyError):
        store.chrome(0)


def test_chrome_trace_shape_and_counter_sum():
    spans = [Span("queue_wait", "serving", 0.0, 0.1),
             Span("decode_block[0]", "decode", 0.1, 0.5, {"block": 0}),
             Span("emit", "serving", 0.5, 0.6)]
    trace = _fake_decode_trace()
    out = chrome_trace(5, spans, trace, {"rid": 5})
    json.dumps(out)                               # wire-safe
    events = out["traceEvents"]
    assert out["displayTimeUnit"] == "ms"
    names = {e["name"] for e in events}
    assert {"queue_wait", "decode_block[0]", "emit"} <= names
    counters = [e for e in events if e.get("ph") == "C"]
    assert len(counters) == trace.steps
    assert sum(e["args"]["commits"] for e in counters) == \
        int((trace.commit_step >= 0).sum())
    # device events sit inside the decode spans' extent, on their own tid
    device = [e for e in events if e.get("cat") == "device"
              and e.get("ph") == "X"]
    assert all(0.1e6 <= e["ts"] <= 0.5e6 for e in device)
    assert len({e["tid"] for e in device}) == 1


def test_trace_view_renders_terminal_table(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "trace_view.py"))
    view = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(view)
    out = chrome_trace(1, [Span("decode_block[0]", "decode", 0.0, 1.0)],
                       _fake_decode_trace(), {"rid": 1})
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(out))
    buf = io.StringIO()
    view.render(view.load(str(path)), out=buf)
    text = buf.getvalue()
    assert "decode_block[0]" in text
    assert "total committed tokens: 8" in text


# --------------------------------------------------------------------------
# the serving stack end to end
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server(params):
    router = ModelRouter(RouterConfig())
    router.register("tiny", lambda: ServingEngine(params, CFG, DCFG,
                                                  max_batch=4))
    handle = ServerThread(router, ServerConfig(port=0)).start()
    yield handle
    handle.stop()


@pytest.fixture()
def client(server):
    return ServingClient(server.host, server.port)


def test_server_trace_end_to_end(client):
    done = client.generate(PROMPT, trace=True, wait=True)
    rid = done["rid"]
    trace = client.trace(rid)
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    assert "queue_wait" in names and "batch_assembly" in names
    assert any(n.startswith("decode_block[") for n in names)
    assert "emit" in names
    # the on-device counters are present and sum to tokens_generated
    commits = sum(e["args"]["commits"] for e in events
                  if e.get("ph") == "C" and e["name"] == "commits")
    assert commits == done["stats"]["tokens_generated"] \
        == DCFG.gen_length
    assert trace["otherData"]["strategy"] == "probability"


def test_server_trace_off_spans_only(client):
    done = client.generate(PROMPT, wait=True)
    trace = client.trace(done["rid"])
    assert any(e["name"] == "queue_wait"
               for e in trace["traceEvents"])
    assert not any(e.get("cat") == "device"
                   for e in trace["traceEvents"])


def test_server_trace_errors(client):
    with pytest.raises(ServerError) as e:
        client.trace(10 ** 9)
    assert e.value.status == 404
    with pytest.raises(ServerError) as e:
        client.generate(PROMPT, trace="yes")      # type: ignore[arg-type]
    assert e.value.status == 400


def test_metrics_exposition_conformance(client):
    client.generate(PROMPT, wait=True)            # ensure decode counters
    text = client.metrics_text()
    lines = text.splitlines()
    assert "repro_up 1" in lines
    # every sample line belongs to a family declared with # TYPE first
    declared = set()
    for line in lines:
        if line.startswith("# TYPE "):
            declared.add(line.split()[2])
        elif line and not line.startswith("#"):
            base = line.split("{")[0].split(" ")[0]
            family = base
            for suffix in ("_bucket", "_sum", "_count"):
                if base.endswith(suffix) and base[: -len(suffix)] \
                        in declared:
                    family = base[: -len(suffix)]
            assert family in declared, line
    # seed-era series survive the registry rewrite verbatim
    assert any(l.startswith('repro_queue_depth{model="tiny"}')
               for l in lines)
    assert any(l.startswith("repro_decode_cache_entries")
               for l in lines)
    assert any(l.startswith(
        'repro_requests_finished_total{model="tiny"}') for l in lines)
    # the new registry instruments are live
    assert any(l.startswith('repro_request_latency_seconds_bucket'
                            '{model="tiny",le=') for l in lines)
    assert any(l.startswith('repro_decodes_total{model="tiny",'
                            'strategy="probability"') for l in lines)


def test_concurrent_metrics_scrape_during_decode(client):
    """/metrics stays scrapeable while a decode is in flight: the
    registry lock never waits on the decode thread."""
    sub = client.generate(PROMPT, trace=True, wait=False)
    texts, stop = [], threading.Event()

    def scrape():
        while not stop.is_set():
            texts.append(client.metrics_text())

    t = threading.Thread(target=scrape)
    t.start()
    try:
        events = list(client.stream(sub["rid"]))
    finally:
        stop.set()
        t.join()
    assert events[-1][0] == "done"
    assert texts and all("repro_up 1" in x for x in texts)
    final = client.metrics_text()
    assert 'repro_tokens_per_request_count{model="tiny"}' in final


# --------------------------------------------------------------------------
# host stages on the profiler's clock, the span tree, the compile counter
# --------------------------------------------------------------------------

def _serve_one_batch(params, dcfg, n=2):
    """``n`` requests submitted before the worker starts, so one batch
    decodes them all; returns each request's exported trace events."""
    async def main():
        sched = AsyncScheduler(ServingEngine(params, CFG, dcfg,
                                             max_batch=4))
        rids = [sched.submit(np.array(PROMPT)) for _ in range(n)]
        await sched.start()
        for rid in rids:
            assert (await sched.result(rid))["status"] == "ok"
        traces = [sched.trace(rid)["traceEvents"] for rid in rids]
        await sched.close()
        return traces
    return asyncio.run(main())


def _spans(events):
    return [e for e in events if e.get("ph") == "X"
            and e.get("cat") != "device"]


def test_span_parents_form_the_stated_tree(params):
    dcfg = dataclasses.replace(DCFG, cache_policy="prefix")
    events = _spans(_serve_one_batch(params, dcfg, n=1)[0])
    by_name = {e["name"]: e for e in events}
    nblocks = DCFG.gen_length // DCFG.block_size

    def parent(name):
        return by_name[name]["args"]["parent"]

    for root in ("queue_wait", "batch_assembly", "decode_finish"):
        assert parent(root) is None
    for i in range(nblocks):
        blk = by_name[f"decode_block[{i}]"]
        assert blk["args"]["parent"] is None
        assert blk["args"]["block"] == i and "compiles" in blk["args"]
        for stage in ("dispatch", "cache_refresh", "device_wait",
                      "validate", "fanout"):
            assert parent(f"{stage}[{i}]") == blk["args"]["id"], stage
        # the engine's stages run inside the round trip that holds them
        for stage in ("dispatch", "cache_refresh", "device_wait",
                      "validate"):
            e = by_name[f"{stage}[{i}]"]
            assert blk["ts"] <= e["ts"] and \
                e["ts"] + e["dur"] <= blk["ts"] + blk["dur"] + 1.0
        assert by_name[f"fanout[{i}]"]["ts"] >= blk["ts"] + blk["dur"] - 1.0
    fin = by_name["decode_finish"]
    assert "compiles" in fin["args"]
    assert parent("finish") == parent("emit") == fin["args"]["id"]
    # cache_refresh keeps its name, category and args
    assert by_name["cache_refresh[0]"]["cat"] == "decode"
    assert by_name["cache_refresh[0]"]["args"]["block"] == 0
    ids = [e["args"]["id"] for e in events]
    assert len(ids) == len(set(ids))


def test_shared_spans_export_identical_ts_across_a_batch(params):
    a, b = (_spans(t) for t in _serve_one_batch(params, DCFG, n=2))
    shared = {e["name"]: e for e in a}
    other = {e["name"]: e for e in b}
    names = set(shared) - {"queue_wait", "emit"}
    assert {"batch_assembly", "decode_block[0]", "dispatch[0]",
            "device_wait[1]", "finish"} <= names
    for name in names:
        assert (shared[name]["ts"], shared[name]["dur"],
                shared[name]["args"]["id"]) == \
            (other[name]["ts"], other[name]["dur"],
             other[name]["args"]["id"]), name
    # one clock for every request: no per-request origin
    assert shared["queue_wait"]["ts"] != 0.0 and \
        other["queue_wait"]["ts"] != 0.0


def _host_events(xplane: str):
    """{name: [(start_ns, end_ns)]} of the host planes' events on the
    profiler's absolute clock: each event's offset from the session's
    ``profile_start_time`` (the ``Task Environment`` plane) added back."""
    pd = jax.profiler.ProfileData.from_file(xplane)
    start = [dict(p.stats)["profile_start_time"] for p in pd.planes
             if p.name == "Task Environment"][0]
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (start + ev.start_ns,
                     start + ev.start_ns + ev.duration_ns))
    return out


def test_profiler_window_shows_the_stages_on_the_span_clock(client,
                                                            tmp_path):
    client.generate(PROMPT, wait=True)              # compiled already
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        done = client.generate(PROMPT, wait=True)
    finally:
        jax.profiler.stop_trace()
    spans = {e["name"]: e for e in _spans(
        client.trace(done["rid"])["traceEvents"])}
    host = _host_events(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                  recursive=True)[0])
    for stage in ("dispatch[0]", "device_wait[0]", "fanout[0]", "finish",
                  "decode_block[0]", "emit"):
        (lo, hi), = host[f"repro/{stage}"]
        span = spans[stage]
        assert abs(lo - span["ts"] * 1e3) < 1e6, stage         # 1 ms
        assert abs(hi - (span["ts"] + span["dur"]) * 1e3) < 1e6, stage
    # recorded after the fact: no mirror
    assert "repro/queue_wait" not in host


def test_compile_counter_sees_a_forced_recompile(client):
    counter = compile_counter()
    f = jax.jit(lambda x: x * 3 + 1)
    before = counter.total()
    f(np.ones((7, 3), np.float32))
    seen = counter.total()
    assert seen > before
    f(np.ones((7, 3), np.float32))                 # cached: nothing new
    assert counter.total() == seen
    f(np.ones((5, 3), np.float32))                 # a new shape recompiles
    assert counter.total() > seen
    # a new decode config compiles inside its first round trip; the same
    # config again compiles nothing
    spans = []
    for _ in range(2):
        done = client.generate(PROMPT, steps=8, wait=True)
        spans.append({e["name"]: e for e in _spans(
            client.trace(done["rid"])["traceEvents"])})
    assert spans[0]["decode_block[0]"]["args"]["compiles"] > 0
    assert all(s["args"]["compiles"] == 0 for s in spans[1].values()
               if s["name"].startswith(("decode_block", "decode_finish")))
    text = client.metrics_text()
    for event in ("trace", "backend_compile", "cache_load"):
        assert f'repro_compiles_total{{event="{event}"}}' in text


def test_compile_counter_loses_no_count_across_threads():
    """Compiles report from whichever thread compiles: concurrent
    reports all count."""
    counter = CompileCounter()
    event = "/jax/core/compile/backend_compile_duration"
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [counter.on_event(event, 0.1)
                            for _ in range(2000)]) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert counter.snapshot() == {"trace": 0, "backend_compile": 32000,
                                  "cache_load": 0}


@pytest.mark.parametrize("policy,scopes", [
    ("none", {"embed", "attention", "mlp", "lm_head", "plan", "search",
              "commit", "confidence"}),
    ("prefix", {"capture_cache", "forward_cached", "embed", "attention",
                "mlp", "lm_head", "plan", "search", "commit",
                "confidence"})])
def test_served_block_program_carries_named_scopes(params, policy, scopes):
    dcfg = DecodeConfig(gen_length=16, block_size=8, steps=16, k1=2,
                        strategy="fdm_a", cache_policy=policy)
    lowered = Decoder(params, CFG, dcfg).lower_blocks(2, 8)
    text = "\n".join(low.compiler_ir("hlo").get_hlo_module().to_string()
                     for low in lowered.values())
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert scopes <= {seg for p in paths for seg in p.split("/")}
    # the K-candidate forward's head sits under the search scope, the
    # plan's scoring under the plan scope
    assert any("/search/" in p and "/lm_head/" in p for p in paths)
    assert any("/plan/confidence/" in p for p in paths)


# --------------------------------------------------------------------------
# ANA105: the telemetry contract
# --------------------------------------------------------------------------

def test_ana105_rule_registered():
    from repro.analysis.findings import RULES
    severity, _ = RULES["ANA105"]
    assert severity == "error"


def test_ana105_clean_for_stock_strategy():
    from repro.analysis.conformance import check_trace_telemetry
    assert check_trace_telemetry("probability") == []
