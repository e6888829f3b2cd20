"""Shared benchmark harness: train one model per task once (cached), then
evaluate decoding strategies on held-out prompts.

The quality testbed is the band-2 gate from DESIGN.md: small masked-
diffusion LMs trained from scratch on bidirectionally-constrained synthetic
tasks; we reproduce the paper's *orderings* (FDM > heuristics, FDM-A ≈ FDM
accuracy at higher speed), not its absolute benchmark numbers — those need
the 8B public checkpoints this container cannot load.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import DecodeConfig, TrainConfig, get_config
from repro.core import Decoder
from repro.data import CharTokenizer, TaskDataset
from repro.training import load, save, train

CKPT_DIR = os.environ.get(
    "REPRO_BENCH_CKPTS",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".bench_ckpts"))
ARCH = os.environ.get("REPRO_BENCH_ARCH", "llada-8b")
# per-task training budgets, calibrated so the decode-order effect is
# visible: hard tasks (carry chains, parities) train long enough to be
# competent; easy tasks stay deliberately light so confidence ordering
# still matters (a saturated model decodes correctly in ANY order).
TASK_STEPS = {"sum": 600, "parity": 1000, "bracket": 1000,
              "sort": 300, "reverse": 250}
TRAIN_STEPS = int(os.environ.get("REPRO_BENCH_TRAIN_STEPS", "0"))
EVAL_N = int(os.environ.get("REPRO_BENCH_EVAL_N", "64"))
# decode-loop driver: fused (device-resident lax.while_loop, the default)
# vs the legacy host step loop; REPRO_HOST_LOOP=1 flips every suite to the
# host loop for A/B runs (benchmarks/loop_overhead.py measures both).
FUSED_LOOP = not bool(int(os.environ.get("REPRO_HOST_LOOP", "0")))

# evaluated model: the paper's own arch family at testbed scale
_MODEL_OVERRIDES = dict(num_layers=4, d_model=256, num_heads=4,
                        num_kv_heads=4, d_ff=1024)


def bench_config(arch: str = None):
    cfg = get_config(arch or ARCH).reduced(**_MODEL_OVERRIDES)
    return cfg


@functools.lru_cache(maxsize=None)
def trained_model(task: str, arch: Optional[str] = None,
                  steps: int = 0) -> Tuple:
    """Train (or load the cached) testbed model for ``task``."""
    cfg = bench_config(arch)
    tok = CharTokenizer(cfg.vocab_size)
    ds = TaskDataset(task, tok)
    steps = steps or TRAIN_STEPS or TASK_STEPS.get(task, 400)
    path = os.path.join(CKPT_DIR, f"{cfg.name}-{task}-{steps}.npz")
    tcfg = TrainConfig(batch_size=64, seq_len=ds.seq_len, steps=steps,
                       log_every=max(steps // 5, 1))
    if os.path.exists(path):
        from repro.models.model import init_model
        template = init_model(jax.random.PRNGKey(0), cfg)
        params, _, _ = load(path, template)
    else:
        print(f"  [train] {cfg.name} on '{task}' for {steps} steps …")
        params, _ = train(cfg, tcfg, ds.batches(tcfg.batch_size), log=None)
        save(path, params, step=steps)
    return params, cfg, ds, tok


def evaluate_strategy(task: str, strategy: str, n_eval: int = 0,
                      seed: int = 0, arch: Optional[str] = None,
                      batch_size: int = 0,
                      **dcfg_over) -> Dict[str, float]:
    """Accuracy (exact match) + TPS + tokens/forward for one strategy.

    ``batch_size`` (default 0 = all of ``n_eval`` in one batch) chops the
    eval set into smaller decode batches.  Forward-skipping strategies
    need this: a batched forward can only be skipped when EVERY row in
    the batch is skippable, so the per-request regime (serving latency,
    ``batch_size=1``) is where extrapolation's savings live — and a fair
    A/B runs the baseline at the same batch size.
    """
    params, cfg, ds, tok = trained_model(task, arch)
    n_eval = n_eval or EVAL_N
    batch = ds.eval_batch(n_eval)
    prompts = jnp.asarray(ds.prompts_only(batch))
    gen = ds.seq_len - prompts.shape[1]
    block = gen if gen <= 16 else max(gen // 2, 1)
    over = dict(gen_length=gen, block_size=block, steps=gen,
                strategy=strategy, fused_loop=FUSED_LOOP)
    over.update(dcfg_over)
    dcfg = DecodeConfig(**over)
    # params-mode Decoder: runners come from the weak cross-call cache
    # keyed on the (lru-cached) trained params, so every strategy suite
    # over the same task model shares compilations
    decoder = Decoder(params, cfg, dcfg)
    bs = batch_size or n_eval
    # warmup compile (excluded from timing) — both chunk shapes: the main
    # batch and any trailing partial chunk, so no trace lands in the loop
    decoder.generate(jax.random.PRNGKey(99), prompts[:bs])
    if n_eval % bs:
        decoder.generate(jax.random.PRNGKey(98), prompts[:n_eval % bs])
    outs, steps, fwd, skipped, revoked, wall = [], 0, 0.0, 0.0, 0.0, 0.0
    for i in range(0, n_eval, bs):
        out, stats = decoder.generate(jax.random.PRNGKey(seed + i),
                                      prompts[i:i + bs])
        outs.append(np.asarray(jax.device_get(out)))
        steps += stats.steps
        fwd += stats.forward_equivalents
        skipped += stats.skipped_forwards
        revoked += stats.revocations
        wall += stats.wall_time
    out_all = np.concatenate(outs, axis=0)
    em = ds.exact_match(out_all, batch)
    return {**{k: v for k, v in dcfg_over.items()},
            "task": task, "strategy": strategy, "accuracy": em,
            "tps": out_all.shape[0] * gen / max(wall, 1e-9),
            "steps": steps,
            "tokens_per_forward": out_all.shape[0] * gen / max(fwd, 1),
            "forward_equivalents": fwd,
            "skipped_forwards": skipped,
            "revocations": revoked}


def print_table(rows, cols) -> None:
    widths = [max(len(str(r.get(c, ""))) for r in rows + [{c: c}])
              for c in cols]
    line = "  ".join(c.ljust(w) for c, w in zip(cols, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(w)
                        for c, w in zip(cols, widths)))


def fmt(rows):
    out = []
    for r in rows:
        r = dict(r)
        r["accuracy"] = f"{r['accuracy']:.2%}"
        r["tps"] = f"{r['tps']:.1f}"
        if "tokens_per_forward" in r:
            r["tokens_per_forward"] = f"{r['tokens_per_forward']:.2f}"
        out.append(r)
    return out
