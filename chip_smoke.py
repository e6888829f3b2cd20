"""Serve LLaDA-8B-width requests on one TPU chip, end to end.

    python chip_smoke.py [--seed 0]

Builds ``llada-8b`` at every published width (d_model 4096, 32 heads of
128, d_ff 12288, vocab 126464) with seeded random weights, cutting only
depth: the largest layer count, at most 8, whose served decode programs
the compiler says fit the chip with 2 GB to spare.  The model is
registered with ``ModelRouter`` behind ``ServerThread`` — the stack
``repro.launch.serve`` boots — and four requests go through
``ServingClient``: two co-batched ``fdm_a`` requests without a cache, one
``fdm`` request (K=2) streamed over SSE, and one ``fdm_a`` request with
the prefix cache.  Then the fused confidence kernel is checked against
the jnp scoring on the logits of one real forward at full vocabulary.

Everything runs in this one process, which holds the chip.  Without a
TPU it exits non-zero before building anything.  Any failed check exits
non-zero; on success the last line of stdout is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

PROMPT_LEN = 128
GEN_LENGTH = 128
BLOCK_SIZE = 32
STEPS = 128
MAX_BATCH = 2
MAX_LAYERS = 8
SPARE_BYTES = 2 * 10**9
# Both device paths are measured against float64 host scores of the same
# float32 logits.  On a v5e the kernel's max_prob = 1/s came within
# 8.0e-6 relative of them and the jnp path's within 1.03e-4: the jnp
# max_prob is exp(-log s), and the chip's float32 log is off by about
# 1e-4 absolute near log s ~ 8.  An error d in log Z moves neg_entropy =
# E[l] - log Z by d (kernel) or, through p = exp(l - log Z), by about
# d * |H| (jnp): d relative either way.  The margin p1 - p2 carries at
# most 2d relative to max_prob.  So max_prob and margin are compared
# relative to max_prob, neg_entropy relative to itself.  Kernel vs jnp
# allows ten times the jnp path's error, kernel vs float64 about ten
# times the kernel's.  A dropped or doubly counted 512-lane tile moves
# the softmax sum by 512 / 126464 = 4e-3, which both catch in max_prob.
JNP_RTOL = 1e-3
F64_RTOL = 1e-4
ARGMAX_ULPS = 4          # argmax may differ only between logits this close


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def served_configs(base_dcfg):
    """The smoke's traffic: (label, request overrides, DecodeConfig)."""
    mixes = [("fdm_a/none", dict(strategy="fdm_a", cache_policy="none")),
             ("fdm/none", dict(strategy="fdm", cache_policy="none")),
             ("fdm_a/prefix", dict(strategy="fdm_a", cache_policy="prefix"))]
    return [(label, over, dataclasses.replace(base_dcfg, **over))
            for label, over in mixes]


def program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


def peak_bytes(dev):
    """The device's peak_bytes_in_use since the process started."""
    return dev.memory_stats().get("peak_bytes_in_use", "not measured")


def choose_depth(dev, base_dcfg):
    """Largest depth <= MAX_LAYERS whose served block programs, compiled
    ahead of time, leave SPARE_BYTES of the device free.  A compile needs
    only shapes, so the probe lowers from the params' shapes and puts
    nothing on the device.  Returns (cfg, report)."""
    import jax
    from repro.configs import get_config
    from repro.core import Decoder
    from repro.models.model import init_model

    limit = dev.memory_stats()["bytes_limit"]
    base = get_config("llada-8b")
    for n in range(MAX_LAYERS, 0, -1):
        cfg = dataclasses.replace(base, num_layers=n)
        probe = jax.eval_shape(
            lambda c=cfg: init_model(jax.random.PRNGKey(0), c))
        need, kernel_in, verdict = {}, {}, None
        t0 = time.perf_counter()
        for label, _, dcfg in served_configs(base_dcfg):
            lowered = Decoder(probe, cfg, dcfg).lower_blocks(MAX_BATCH,
                                                             PROMPT_LEN)
            for part, low in lowered.items():
                key = f"{label}:{part}"
                try:
                    compiled = low.compile()
                except jax.errors.JaxRuntimeError as e:
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        raise
                    verdict = f"{key} does not fit: " \
                              f"{str(e).splitlines()[0]}"
                    break
                need[key] = program_bytes(compiled)
                kernel_in[key] = "tpu_custom_call" in compiled.as_text()
                if limit - need[key] < SPARE_BYTES:
                    verdict = f"{key} needs {need[key]} of {limit} bytes, " \
                              f"spare {limit - need[key]} < {SPARE_BYTES}"
                    break
            if verdict:
                break
        compile_s = time.perf_counter() - t0
        print(f"depth {n}: {verdict or 'fits'}", flush=True)
        if verdict is None:
            worst = max(need, key=need.get)
            return cfg, dict(
                need=need, kernel_in=kernel_in, compile_s=compile_s,
                reason=f"largest served program {worst} needs "
                       f"{need[worst]} of {limit} bytes, spare "
                       f"{limit - need[worst]} >= {SPARE_BYTES} (AOT "
                       f"memory_analysis)" +
                       ("" if n == MAX_LAYERS else
                        f"; depth {n + 1} did not fit"))
    raise SmokeFailure("no depth fits the chip with the required spare")


def serve_requests(params, cfg, base_dcfg, seed: int):
    """Send the four requests through the HTTP stack; returns
    {label: (prompt, terminal event, block events)} and co-batch sizes."""
    import numpy as np
    from repro.configs import RouterConfig, ServerConfig
    from repro.serving import (ModelRouter, ServerThread, ServingClient,
                               ServingEngine)

    router = ModelRouter(RouterConfig())
    router.register(cfg.name, lambda: ServingEngine(
        params, cfg, base_dcfg, max_batch=MAX_BATCH, seed=seed))
    handle = ServerThread(router, ServerConfig(port=0)).start()
    rs = np.random.RandomState(seed)
    geometry = dict(gen_length=GEN_LENGTH, block_size=BLOCK_SIZE,
                    steps=STEPS)
    by_label = {label: over for label, over, _ in
                served_configs(base_dcfg)}
    plan = [("fdm", by_label["fdm/none"]),
            ("fdm_a/none#1", by_label["fdm_a/none"]),
            ("fdm_a/none#2", by_label["fdm_a/none"]),
            ("fdm_a/prefix", by_label["fdm_a/prefix"])]
    try:
        client = ServingClient(handle.host, handle.port, timeout=1200.0)
        rids = {}
        for label, over in plan:
            # the mask id is vocab - 1: prompts draw below it
            prompt = rs.randint(0, cfg.mask_token_id, PROMPT_LEN).tolist()
            sub = client.generate(prompt, model=cfg.name, wait=False,
                                  **geometry, **over)
            rids[label] = (sub["rid"], prompt)
        results, cobatch = {}, {}
        for label, (rid, prompt) in rids.items():
            blocks, final = [], None
            for name, event in client.stream(rid, model=cfg.name):
                if event.get("final"):
                    final = event
                elif name == "block":
                    blocks.append(event)
            results[label] = (prompt, final, blocks)
            spans = client.trace(rid, model=cfg.name)["traceEvents"]
            cobatch[label] = [e["args"]["batch_size"] for e in spans
                              if e.get("name") == "batch_assembly"]
        return results, cobatch
    finally:
        handle.stop()


def check_results(results, cobatch, mask_id: int) -> None:
    for label, (prompt, final, blocks) in results.items():
        check(final is not None, f"{label}: stream ended without a "
                                 f"terminal event")
        check(final.get("status") == "ok",
              f"{label}: terminal status {final.get('status')!r} "
              f"({final.get('error', '')})")
        toks = final["tokens"]
        check(len(toks) == PROMPT_LEN + GEN_LENGTH,
              f"{label}: {len(toks)} tokens, want prompt {PROMPT_LEN} + "
              f"gen {GEN_LENGTH}")
        check(toks[:PROMPT_LEN] == prompt, f"{label}: prompt not kept")
        gen = toks[PROMPT_LEN:]
        check(len(gen) == GEN_LENGTH and mask_id not in gen,
              f"{label}: {gen.count(mask_id)} mask ids left")
        check(len(blocks) == GEN_LENGTH // BLOCK_SIZE,
              f"{label}: {len(blocks)} block events over SSE")
    for label in ("fdm_a/none#1", "fdm_a/none#2"):
        check(cobatch[label] == [2],
              f"{label}: decoded in batches of {cobatch[label]}, want one "
              f"co-batch of 2")


def exact_scores(lg):
    """float64 host scores of float32 logits (R, V): the yardstick both
    device paths are measured against."""
    import numpy as np
    x = lg.astype(np.float64)
    m = x.max(axis=1, keepdims=True)
    e = np.exp(x - m)
    s = e.sum(axis=1, keepdims=True)
    p = e / s
    top2 = np.sort(p, axis=1)[:, -2:]
    logp = x - m - np.log(s)
    return dict(max_prob=top2[:, 1], margin=top2[:, 1] - top2[:, 0],
                neg_entropy=(p * logp).sum(axis=1))


def score_errors(got, want, exact):
    """Largest relative error of each score: max_prob and margin relative
    to the exact max_prob, neg_entropy relative to the exact one."""
    import numpy as np
    err = lambda k: np.abs(np.asarray(got[k], np.float64)  # noqa: E731
                           - np.asarray(want[k], np.float64))
    scale = dict(max_prob=exact["max_prob"], margin=exact["max_prob"],
                 neg_entropy=np.abs(exact["neg_entropy"]))
    return {k: float(np.max(err(k) / v)) for k, v in scale.items()}


def check_kernel(params, cfg, tokens):
    """Score one real forward's logits with the kernel and with jnp;
    returns (compiled kernel program text, report)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.confidence import score_logits
    from repro.models.model import forward

    logits = jax.jit(lambda p, t: forward(p, t, cfg)[0])(
        params, jnp.asarray(tokens, jnp.int32)[None])
    check(logits.shape == (1, len(tokens), cfg.vocab_size),
          f"logits shape {logits.shape}")
    fused = jax.jit(lambda x: score_logits(x, use_kernel=True))
    compiled = fused.lower(logits).compile()
    kern = jax.device_get(compiled(logits))._asdict()
    ref = jax.device_get(jax.jit(
        lambda x: score_logits(x, use_kernel=False))(logits))._asdict()
    lg = np.asarray(logits[0])
    kern = {k: np.asarray(v)[0] for k, v in kern.items()}
    ref = {k: np.asarray(v)[0] for k, v in ref.items()}
    exact = exact_scores(lg)
    rows = np.arange(lg.shape[0])
    top = lg[rows, ref["argmax"]]
    gap = np.abs(lg[rows, kern["argmax"]] - top)
    differ = kern["argmax"] != ref["argmax"]
    report = dict(
        argmax_differ=int(differ.sum()),
        kernel_vs_jnp=score_errors(kern, ref, exact),
        kernel_vs_f64=score_errors(kern, exact, exact),
        jnp_vs_f64=score_errors(ref, exact, exact),
        max_prob_range=[float(exact["max_prob"].min()),
                        float(exact["max_prob"].max())])
    print(f"kernel vs jnp at vocab {cfg.vocab_size}: {json.dumps(report)}",
          flush=True)
    check(np.isfinite(lg).all(), "non-finite logits")
    for k in ("max_prob", "margin", "neg_entropy"):
        check(np.isfinite(kern[k]).all(), f"non-finite kernel {k}")
    check(np.all(gap[differ] <= ARGMAX_ULPS * np.spacing(np.abs(top[differ]))),
          f"argmax differs beyond rounding at {np.flatnonzero(differ)}")
    for against, tol in (("jnp", JNP_RTOL), ("f64", F64_RTOL)):
        for k, e in report[f"kernel_vs_{against}"].items():
            check(e <= tol, f"kernel {k} vs {against}: relative error {e} "
                            f"> {tol}")
    return compiled.as_text(), report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU: JAX found {dev.platform!r} devices; this "
              f"smoke runs only on the chip", file=sys.stderr)
        return 2
    try:
        from repro.configs import DecodeConfig
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models.model import init_model
        from repro.serving import params_bytes
    except ImportError as e:
        print(f"FAIL: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    base_dcfg = DecodeConfig(gen_length=GEN_LENGTH, block_size=BLOCK_SIZE,
                             steps=STEPS, strategy="fdm_a", k=2)
    print(f"device: {dev.device_kind} x{len(jax.devices())}, compile "
          f"cache {cache_dir}", flush=True)
    try:
        cfg, depth = choose_depth(dev, base_dcfg)
        print(f"N = {cfg.num_layers} layers: {depth['reason']}",
              flush=True)
        for key, need in depth["need"].items():
            print(f"AOT memory_analysis {key}: {need} bytes")
        print(f"AOT compile seconds of the served programs at depth "
              f"{cfg.num_layers}: {depth['compile_s']:.3f}")
        for key, has_kernel in depth["kernel_in"].items():
            check(key.endswith(":refresh") or has_kernel,
                  f"served program {key} has no tpu_custom_call")
        peak = {"depth probe": peak_bytes(dev)}
        params = init_model(jax.random.PRNGKey(args.seed), cfg)
        jax.block_until_ready(params)
        peak["init_model"] = peak_bytes(dev)
        print(f"model: llada-8b widths, {cfg.num_layers} layers, param "
              f"bytes {params_bytes(params)}", flush=True)
        t0 = time.perf_counter()
        results, cobatch = serve_requests(params, cfg, base_dcfg,
                                          args.seed)
        serve_s = time.perf_counter() - t0
        peak["serving"] = peak_bytes(dev)
        for label, (_, final, blocks) in results.items():
            final = final or {}
            print(f"request {label}: status {final.get('status')}, "
                  f"latency_s {final.get('latency_s')} (server-side, "
                  f"submit to finish, compiles included), stats "
                  f"{json.dumps(final.get('stats'))} (wall_time_s: the "
                  f"batch's decode, first call's compile included, over "
                  f"its real requests), batch sizes {cobatch[label]}, "
                  f"{len(blocks)} SSE block events")
        print(f"four requests served in {serve_s:.3f} s wall", flush=True)
        check_results(results, cobatch, cfg.mask_token_id)
        text, _ = check_kernel(params, cfg,
                               results["fdm_a/none#1"][1]["tokens"])
        check("tpu_custom_call" in text,
              "scoring program has no tpu_custom_call: the kernel fell "
              "back")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    peak["kernel check"] = peak_bytes(dev)
    # the peak only grows: a phase set it only where it rose past the last
    print(f"device bytes_limit {dev.memory_stats()['bytes_limit']}; "
          f"peak_bytes_in_use after each phase: {json.dumps(peak)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
