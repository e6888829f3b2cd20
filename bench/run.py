"""One run of one benchmark cell, on the chip this process holds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration names its model
family (``bench/families/<family>.py``: the program check, the weights'
leaves, the operation count's terms and the plain reference); the
cell's limits are in ``bench/limits/<cell>.json`` and its per-layer
metrics are the readers ``bench/metrics/<metric>.py`` that
``BENCHMARK.json`` lists for it.

Set-up builds the weights on the device from the seed, warms up the
programs the cell's shapes use, and starts the server; ``setup_s`` runs
from the start of this process to the opening of the window.  The
window offers the mix's load for ``--seconds`` through the HTTP/SSE
front end (``ServerThread`` -> ``AsyncScheduler`` -> ``ServingEngine``
-> ``Decoder`` -> the model and the confidence kernel), timed from the
client's side.  Then the peak device memory is read, the server and the
weights are dropped, and the family's plain reference replays a seeded
sample of the finished requests (``bench/reference.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the correctness check
compares, beside its limit.  The same numbers are the last lines on
stderr.  Without a TPU, or with fewer chips than the cell asks for, the
run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time

from bench.families import SetupError

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

CONFIGS = os.path.join(BENCH, "configs")
FAMILIES = os.path.join(BENCH, "families")
_modules: dict = {}


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    """The module of the file at ``path``, loaded once."""
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _modules[path] = module
    return _modules[path]


def load_config(name: str) -> dict:
    return _json(os.path.join(CONFIGS, name + ".json"))


def load_family(name: str):
    """The family module ``<FAMILIES>/<name>.py`` (bench/families/)."""
    return _module(os.path.join(FAMILIES, name + ".py"),
                   "bench_family_" + name)


def load_cell(workload: str):
    """(spec, cell, config, mix, limits) for a workload name."""
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config = load_config(cell["config"])
    mix = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(BENCH, "limits", workload + ".json"))
    return spec, cell, config, mix, limits


def require_accelerator(chips: int):
    """The devices the cell runs on; ``SetupError`` without a TPU or with
    fewer chips than asked.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devices[0].platform!r} "
                         f"devices; the benchmark runs only on the chip")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def per_layer_readers(spec: dict, workload: str) -> dict:
    """{metric entry name: (entry, read function)} of the cell's
    per-layer metrics, each loaded from bench/metrics/<name>.py."""
    out = {}
    for entry in spec["per_layer"]:
        if workload not in entry.get("workloads", [workload]):
            continue
        name = entry["name"]
        module = _module(os.path.join(BENCH, "metrics", name + ".py"),
                         "bench_metric_" + name.replace(".", "_"))
        out[name] = (entry, module.read)
    return out


# -- the program --------------------------------------------------------------

def check_layout(params, cfg) -> None:
    """The seeded tree has exactly the program's parameter paths and
    shapes (dtypes differ on purpose: the program builds float32)."""
    import jax
    from bench import weights
    from repro.models.model import init_model
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    want = {k: tuple(v.shape) for k, v in weights.flatten(want).items()}
    got = {k: tuple(v.shape) for k, v in weights.flatten(params).items()}
    if want != got:
        raise SetupError(f"seeded weights do not match the program's "
                         f"layout: {sorted(set(want.items()) ^ set(got.items()))}")


def decode_config(mix: dict):
    from repro.configs import DecodeConfig
    return DecodeConfig(**mix["decode"])


def warm_up(params, cfg, dcfg, mix: dict) -> None:
    """Compile (or load) every program the cell's traffic uses: for each
    prompt length, the first block of the served decode (the refresh
    and the cached window step under a cache policy), and the eager
    slices the engine takes of each block."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Decoder
    bs = dcfg.block_size
    for lp in sorted(int(x) for x in mix["prompt_lengths"]):
        prompts = jnp.zeros((mix["max_batch"], lp), jnp.int32)
        blocks = Decoder(params, cfg, dcfg).generate_blocks(
            jax.random.PRNGKey(0), prompts)
        ev = next(blocks)
        for blk in range(dcfg.gen_length // bs):
            np.asarray(ev.x[:, lp + blk * bs:lp + (blk + 1) * bs])
        blocks.close()
    # the engine splits its key per batch and unpacks the pair
    key, sub = jax.random.split(jax.random.PRNGKey(0))
    jax.block_until_ready((key, sub))


class CompileCounter:
    """Counts, while ``active``, the programs JAX traces, compiles or
    loads from the persistent cache: a program built inside the window
    stalls the served path there, whichever of the three it takes."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {name.rsplit("/", 1)[1]: 0 for name in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[1]] += 1


def start_server(params, cfg, dcfg, mix: dict, seed: int):
    from repro.configs import DegradeConfig, RouterConfig, ServerConfig
    from repro.serving import ModelRouter, ServerThread, ServingEngine
    from bench.weights import seed_key
    import jax
    engine_seed = int(jax.device_get(seed_key(seed, "engine"))[1])
    router = ModelRouter(RouterConfig())
    router.register(cfg.name, lambda: ServingEngine(
        params, cfg, dcfg, max_batch=mix["max_batch"], seed=engine_seed))
    # the ladder would cheapen step budgets under pressure: the cells
    # measure the configured decode, so it stays off
    scfg = ServerConfig(port=0, max_queue_depth=256, stream_retain=1024,
                        degrade=DegradeConfig(enabled=False))
    handle = ServerThread(router, scfg).start()
    handle.call(handle.server.scheduler, cfg.name)
    return handle, router


# -- the window -----------------------------------------------------------------

class Profile:
    """The traced run's one profiler window, inside the load window."""

    def __init__(self, seconds: float, mix: dict):
        self.offset = min(2.0, 0.1 * seconds)
        self.length = max(min(float(mix["trace_seconds"]),
                              seconds - self.offset - 0.5), 0.5)
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.window = None
        self.error = None
        self._thread = None

    def open(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        daemon=True)
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax
        try:
            time.sleep(max(t0 + self.offset - time.perf_counter(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            start = time.perf_counter()
            time.sleep(self.length)
            self.window = (start, time.perf_counter())
            jax.profiler.stop_trace()
        except Exception as e:          # reported, and the run fails
            self.error = f"{type(e).__name__}: {e}"

    def reduce(self) -> dict:
        import shutil
        from bench import trace_reduce
        if self._thread is not None:
            self._thread.join(timeout=120.0)
        try:
            if self.error or self.window is None:
                raise RuntimeError(f"profiler window failed: {self.error}")
            path = trace_reduce.find_xplane(self.dir)
            return trace_reduce.reduce(path, self.window[1] - self.window[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def completions(records) -> list:
    """Block events grouped into block completions: batches run one at a
    time, and the events of one batch's block (the same block index)
    reach the clients within milliseconds of each other.  Returns
    [[t, ...], ...] in order."""
    groups, index = [], None
    for t, blk in sorted(e for r in records for e in r.blocks):
        if groups and blk == index and t - groups[-1][-1] < 0.05:
            groups[-1].append(t)
        else:
            groups.append([t])
            index = blk
    return groups


def gen_tok_s(records, t0: float, t1: float, block_size: int) -> float:
    """Generated tokens committed in [t0, t1] over its length.  A block
    completion's tokens were produced between the previous completion
    and itself, and count in proportion to that interval's overlap with
    the window."""
    groups = completions(records)
    tokens = 0.0
    for prev, cur in zip(groups, groups[1:]):
        a, b = prev[0], cur[0]
        overlap = max(0.0, min(b, t1) - max(a, t0))
        tokens += block_size * len(cur) * overlap / (b - a)
    return tokens / (t1 - t0)


def block_intervals(records, t0: float, t1: float) -> list:
    """The times between block completions that overlap the window: a
    stall of the served path shows as one long interval, a slower chip
    as all of them longer."""
    starts = [g[0] for g in completions(records)]
    return [round(b - a, 4) for a, b in zip(starts, starts[1:])
            if a < t1 and b > t0]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` stands for a failed request."""
    vals = sorted(values)
    if not vals:
        return float("inf")
    return vals[max(math.ceil(q * len(vals)) - 1, 0)]


def window_requests(load):
    """The requests the window offered: poisson, those due in it;
    backlog, those sent before its close and not cancelled by the
    harness."""
    if load.mix["arrivals"] == "poisson":
        return [r for r in load.records if load.t0 <= r.due < load.t1]
    return [r for r in load.records
            if r.sent < load.t1 and r.status != "cancelled"]


def end_to_end(load, setup_s: float, block_size: int) -> dict:
    out = {"setup_s": {"value": setup_s, "unit": "s"}}
    reqs = window_requests(load)
    if load.mix["arrivals"] == "backlog":
        out["gen_tok_s"] = {"value": gen_tok_s(load.records, load.t0,
                                               load.t1, block_size),
                            "unit": "tokens/s"}
    else:
        inf = float("inf")
        ttfb = [r.blocks[0][0] - r.due if r.blocks else inf for r in reqs]
        lat = [r.final_t - r.due if r.ok else inf for r in reqs]
        out["ttfb_p90_s"] = {"value": percentile(ttfb, 0.9), "unit": "s"}
        out["latency_p90_s"] = {"value": percentile(lat, 0.9), "unit": "s"}
    return out


# -- correctness -----------------------------------------------------------------

def answer_faults(rec, gen: int) -> int:
    """1 if a finished request's answer breaks its shape: the prompt not
    kept or the wrong length."""
    toks = rec.tokens or []
    lp = len(rec.prompt)
    return int(len(toks) != lp + gen or toks[:lp] != rec.prompt.tolist())


def sample(records, mix: dict, seed: int):
    """The finished requests the reference replays: one of the longest
    prompts, then others drawn from the seed."""
    import numpy as np
    from bench.traffic import _rs
    done = [r for r in records if r.ok]
    if not done:
        return []
    rs = _rs(seed, 4)
    longest = max(len(r.prompt) for r in done)
    first = [r for r in done if len(r.prompt) == longest]
    pick = [first[rs.randint(len(first))]]
    rest = [r for r in done if r is not pick[0]]
    n = min(int(mix["check_requests"]) - 1, len(rest))
    pick += [rest[i] for i in sorted(rs.choice(len(rest), n, replace=False))]
    return pick


def check(records, config: dict, family, mix: dict, seed: int,
          limits: dict, control: bool = False):
    """Replay the sample with the reference.  Returns the compared
    numbers; with ``control`` the float8 control's numbers under the same
    limits (else None); and the widest gaps, program's and control's,
    which are reported but not compared: where the replay cannot follow
    the program's commit order they do not separate the two (PERF.md)."""
    import jax.numpy as jnp
    import numpy as np
    from bench import reference, weights
    sizes, dec = config["sizes"], mix["decode"]
    gen, mask_id = dec["gen_length"], sizes["mask_token_id"]
    faults = sum(answer_faults(r, gen) for r in records if r.ok)
    picked = [r for r in sample(records, mix, seed)
              if not answer_faults(r, gen)]
    flat = weights.flatten(weights.make_params(
        family.param_shapes(sizes, config["depth"]), seed,
        jnp.dtype(config["weights_dtype"])))
    gaps, cgaps = [np.zeros(0)], [np.zeros(0)]
    for lp in sorted({len(r.prompt) for r in picked}):
        group = [r for r in picked if len(r.prompt) == lp]
        prompts = np.stack([r.prompt for r in group])
        served = np.array([r.tokens[lp:] for r in group], np.int32)
        g, cg = reference.replay(family, flat, sizes, prompts, served, dec,
                                 mask_id, control)
        gaps.append(g.ravel())
        cgaps.append(cg.ravel())
    g, cg = np.concatenate(gaps), np.concatenate(cgaps)
    served = ~np.isnan(g)

    def widest(x):
        return float(x[served].max()) if served.any() else float("inf")

    def numbers(x):
        x = x[served]
        return {"mismatch_share": {
                    "value": float((x > 0).mean()) if x.size else 1.0,
                    "limit": limits["mismatch_share"]},
                "unserved_tokens": {"value": int((~served).sum()),
                                    "limit": limits["unserved_tokens"]},
                "answer_faults": {"value": faults, "limit": 0},
                "replayed_requests": {"value": len(picked),
                                      "limit": int(mix["check_requests"])}}

    gaps = {"widest_gap": widest(g)}
    if control:
        gaps["control_widest_gap"] = widest(cg)
    return numbers(g), numbers(cg) if control else None, gaps


def is_correct(compared: dict) -> bool:
    """Every compared number within its limit; the replayed requests are
    a floor, the others ceilings."""
    return all(c["value"] >= c["limit"] if name == "replayed_requests"
               else c["value"] <= c["limit"]
               for name, c in compared.items())


# -- one run ---------------------------------------------------------------------

def run_cell(spec, workload: str, config: dict, mix: dict, limits: dict,
             seed: int, seconds: float, trace: bool, devices,
             control: bool = False) -> dict:
    """Set up, run one window, check; returns the result object.  With
    ``control`` it also holds, under ``control``, the float8 control's
    compared numbers and whether they would pass."""
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    from bench import load as load_lib
    from bench import weights

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devices[0]
    family = load_family(config["family"])
    cfg = family.program_config(config)
    dcfg = decode_config(mix)
    marks = {"start": time.perf_counter()}
    params = weights.make_params(
        family.param_shapes(config["sizes"], config["depth"]), seed,
        jnp.dtype(config["weights_dtype"]))
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    check_layout(params, cfg)
    warm_up(params, cfg, dcfg, mix)
    marks["warm_up"] = time.perf_counter()
    handle, router = start_server(params, cfg, dcfg, mix, seed)
    marks["server"] = time.perf_counter()
    compiles = CompileCounter()
    profile = Profile(seconds, mix) if trace else None
    load = load_lib.Load(handle.host, handle.port, cfg.name, mix, seed,
                         cfg.mask_token_id, want_spans=trace)
    opened = {}

    def on_open(t0):
        opened["setup_s"] = t0 - T_PROCESS
        compiles.active = True
        if profile is not None:
            profile.open(t0)

    try:
        load.run(seconds, on_open)
    finally:
        compiles.active = False
        handle.stop()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": 0, "failed": 0,
              "metrics": {}, "device": device}
    reqs = window_requests(load)
    result["attempted"] = len(reqs)
    result["failed"] = sum(1 for r in reqs if not r.ok)
    diag = {"compiles_in_window": compiles.counts,
            "max_lateness_s": max(load.lateness_s, default=0.0),
            "finished": sum(1 for r in load.records if r.ok),
            "block_intervals_s": block_intervals(
                load.records, load.t0, load.t1)}
    if trace:
        reduced = profile.reduce()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
        view = RunView(workload, config, family, mix, load.records,
                       load.t0, load.t1, reduced, device_peaks(dev))
        for name, (entry, read) in per_layer_readers(spec,
                                                     workload).items():
            value = read(view)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": entry["unit"]}
    else:
        result["metrics"] = end_to_end(load, opened["setup_s"],
                                       dcfg.block_size)
    # the program's state goes before the reference runs
    del handle, router, params
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    compared, ctrl, gaps = check(load.records, config, family, mix, seed,
                                 limits, control)
    diag.update(gaps)
    diag["setup_parts_s"] = {k: marks[k] - marks[p] for p, k in
                             zip(list(marks), list(marks)[1:])}
    diag["reference_s"] = time.perf_counter() - t_ref
    result["diagnostics"] = diag
    if control:
        result["control"] = {"correct": is_correct(ctrl), "compared": ctrl}
    result["correct"] = is_correct(compared)
    result["compared"] = compared
    return result


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees of a traced run."""
    workload: str
    config: dict
    family: object      # the configuration's bench/families/ module
    mix: dict
    records: list
    t0: float
    t1: float
    trace: dict
    peaks: dict


def device_peaks(dev) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))
    if dev.device_kind not in table:
        raise SetupError(f"no peaks for device kind {dev.device_kind!r} in "
                         f"bench/peaks.json")
    return table[dev.device_kind]


def report(result: dict) -> None:
    """Compared numbers as the last lines of stderr, the result as the
    last line of stdout."""
    for name, v in result.get("diagnostics", {}).items():
        print(f"{name}: {v}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, cell, config, mix, limits = load_cell(args.workload)
        devices = require_accelerator(int(cell["chips"]))
        sys.path.insert(0, os.path.join(ROOT, "src"))
        if importlib.util.find_spec("repro") is None:
            raise SetupError("the program (src/repro) is not beside bench/")
    except (SetupError, OSError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    result = run_cell(spec, args.workload, config, mix, limits, args.seed,
                      args.seconds, bool(args.trace), devices)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
