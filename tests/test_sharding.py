"""Sharding-rule unit tests (AbstractMesh — no 512-device requirement) and
a subprocess integration test for the real dry-run."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.models.model import init_decode_state, init_model
from repro.parallel.sharding import cache_pspecs, param_pspecs

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaf_specs(arch, mesh=MESH):
    cfg = get_config(arch)
    p_sds = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    specs = param_pspecs(p_sds, mesh)
    flat_s, _ = jax.tree_util.tree_flatten_with_path(specs)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(p_sds)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): (leaf.shape, spec)
            for (path, leaf), (_, spec) in zip(flat_p, flat_s)}


def _axis_size(mesh, ax):
    if isinstance(ax, tuple):
        return int(np.prod([mesh.shape[a] for a in ax]))
    return mesh.shape[ax]


@pytest.mark.parametrize("arch", ["qwen3-14b", "mixtral-8x22b",
                                  "deepseek-v2-236b", "whisper-medium",
                                  "xlstm-125m", "hymba-1.5b"])
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["1pod", "2pod"])
def test_param_specs_divisible_and_unique(arch, mesh):
    """Every sharded dim divides its axes; no axis is used twice."""
    for path, (shape, spec) in _leaf_specs(arch, mesh).items():
        used = []
        for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
            if ax is None:
                continue
            assert dim % _axis_size(mesh, ax) == 0, (path, shape, spec)
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                assert a not in used, (path, spec)
                used.append(a)


def test_expert_parallel_when_divisible():
    """DeepSeek: 160 experts % 16 == 0 -> expert axis on `model`."""
    specs = _leaf_specs("deepseek-v2-236b")
    gate = [v for k, v in specs.items() if k.endswith("moe/w_gate")]
    assert gate, "no stacked expert weights found"
    for shape, spec in gate:
        # (layer_stack, E, d, ff) — expert dim carries `model`
        assert spec[-3] == "model", (shape, spec)


def test_mixtral_falls_back_to_ffn_tp():
    """Mixtral: 8 experts % 16 != 0 -> ffn-dim tensor parallelism."""
    specs = _leaf_specs("mixtral-8x22b")
    for k, (shape, spec) in specs.items():
        if k.endswith("moe/w_gate"):
            assert spec[-3] is None, (k, shape, spec)
            assert spec[-1] == "model", (k, shape, spec)


def test_odd_vocab_replicated():
    """Whisper vocab 51865 does not divide 16 -> embed vocab replicated."""
    specs = _leaf_specs("whisper-medium")
    shape, spec = next(v for k, v in specs.items()
                       if k.endswith("embed/tok"))
    assert spec[0] is None


def test_cache_specs_batch_vs_context_parallel():
    cfg = get_config("qwen3-14b")
    state = jax.eval_shape(
        lambda: init_decode_state(cfg, 128, 1024, jnp.bfloat16))
    specs = cache_pspecs(state, MESH, batch=128)
    ks = [s for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)) if len(s) >= 4]
    assert any(("data",) == s[1] or "data" in (s[1] or ()) for s in ks), ks

    # batch=1 (long-context): the long axis gets the data axes instead
    state1 = jax.eval_shape(
        lambda: init_decode_state(cfg, 1, 32768, jnp.bfloat16))
    specs1 = cache_pspecs(state1, MESH, batch=1)
    flat = [s for s in jax.tree.leaves(
        specs1, is_leaf=lambda x: isinstance(x, P)) if len(s) >= 4]
    assert any(s[2] is not None for s in flat), flat


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("REPRO_RUN_SLOW"),
                    reason="~8 min subprocess dry-run; set REPRO_RUN_SLOW=1 "
                           "to include it (verified passing 2026-07)")
def test_dryrun_subprocess_smoke():
    """The real thing, in a subprocess (own XLA device-count flag)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "stablelm-3b", "--shape", "decode_32k", "--skip-full"],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "1 ok, 0 failed" in proc.stdout
