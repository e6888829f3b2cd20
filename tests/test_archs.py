"""Per-architecture smoke tests (assignment contract: reduced variant of
the same family — ≤2 layers, d_model ≤ 512, ≤4 experts — one forward and
one train step on CPU, asserting output shapes and no NaNs).

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct, no
allocation) — see launch/dryrun.py.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, TrainConfig, get_config
from repro.models.model import (decode_step, forward, init_decode_state,
                                init_model)
from repro.training.optimizer import adamw_init
from repro.training.trainer import make_train_step

B, L = 2, 16


def _extras(cfg, rng):
    kw = {}
    if cfg.is_encdec:
        kw["enc_embeds"] = jax.random.normal(
            rng, (B, min(cfg.encdec.encoder_seq, 32) or 32, cfg.d_model))
    if cfg.encdec is not None and cfg.encdec.frontend == "vision_stub":
        kw["patch_embeds"] = jax.random.normal(
            rng, (B, cfg.encdec.num_patch_tokens, cfg.d_model))
    return kw


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS + ["llada-8b"])
def test_reduced_forward_shapes_and_finite(arch, rng):
    cfg = get_config(arch).reduced()
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.moe.num_experts <= 4
    params = init_model(rng, cfg)
    toks = jax.random.randint(rng, (B, L), 0, cfg.vocab_size)
    logits, aux = forward(params, toks, cfg, **_extras(cfg, rng))
    assert logits.shape == (B, L, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    extras = ()
    batch = {"tokens": jax.random.randint(rng, (B, L), 0,
                                          cfg.vocab_size - 1),
             "maskable": jnp.ones((B, L), bool).at[:, :4].set(False)}
    kw = _extras(cfg, rng)
    if "enc_embeds" in kw:
        batch["enc_embeds"] = kw["enc_embeds"]
        extras = ("enc_embeds",)
    if "patch_embeds" in kw:
        batch["patch_embeds"] = kw["patch_embeds"]
        extras = ("patch_embeds",)
    tcfg = TrainConfig(steps=2)
    step = make_train_step(cfg, tcfg, extra_inputs=extras)
    params = init_model(rng, cfg)
    opt = adamw_init(params)
    params2, opt2, metrics = jax.jit(step)(params, opt, rng, batch)
    assert jnp.isfinite(metrics["loss"])
    assert float(metrics["loss"]) > 0
    # at least one parameter actually moved
    moved = any(bool(jnp.any(a != b))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_decode_step(arch, rng):
    cfg = get_config(arch).reduced()
    enc = None
    if cfg.is_encdec:
        enc = jax.random.normal(rng, (B, 32, cfg.d_model))
    params = init_model(rng, cfg)
    state = init_decode_state(cfg, B, L, jnp.float32, enc_out=enc)
    tok = jax.random.randint(rng, (B, 1), 0, cfg.vocab_size - 1)
    pos = jnp.full((B, 1), L - 1, jnp.int32)
    logits, state2 = decode_step(params, tok, pos, state, cfg)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_decode_matches_forward_for_dense(rng):
    """Cached single-token decode must agree with the full forward on the
    same committed sequence.

    Exactness holds for ONE layer only: with deeper stacks the frozen-
    prefix cache is the documented approximation (layer-n K/V of early
    tokens were computed before later tokens existed — see DESIGN.md §3,
    the Fast-dLLM/dKV-cache approximation the paper's related work uses).
    """
    cfg = get_config("stablelm-3b").reduced(num_layers=1)
    params = init_model(rng, cfg)
    toks = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size - 1)
    full_logits, _ = forward(params, toks, cfg)

    # build the cache by decoding tokens 0..6 sequentially, then compare
    # the logits for the final token
    state = init_decode_state(cfg, 1, 8, jnp.float32)
    for i in range(8):
        logits, state = decode_step(params, toks[:, i:i + 1],
                                    jnp.full((1, 1), i, jnp.int32),
                                    state, cfg)
    # position 7 decode sees tokens 0..7 -- forward position 7 sees all 8;
    # bidirectional attention means full forward also attends "future"
    # masked positions, so compare only the *last* position, whose visible
    # set matches.
    import numpy as np
    np.testing.assert_allclose(np.asarray(logits[0, 0]),
                               np.asarray(full_logits[0, 7]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["llada-8b", "deepseek-v2-236b",
                                  "xlstm-125m", "whisper-medium"])
def test_init_model_equals_per_layer_stack(arch, rng):
    """``init_model`` writes each layer into a donated stacked buffer; its
    values must equal stacking the per-layer inits, so checkpoints saved
    from the stacked form stay loadable bit for bit."""
    import numpy as np
    from repro.models import blocks as blocks_lib
    from repro.models.model import _layer_groups, encoder_config

    cfg = get_config(arch).reduced(num_layers=4)
    params = init_model(rng, cfg)
    ks = jax.random.split(rng, cfg.num_layers + 3)

    def stacked(keys, c):
        return [jax.tree.map(lambda *xs: jnp.stack(xs), *[
            blocks_lib.init_block(keys[i], c, i) for i in g])
            for g in _layer_groups(c)]

    want = [stacked(ks[1:], cfg)]
    got = [params["blocks"]]
    if cfg.is_encdec:
        ecfg = encoder_config(cfg)
        want.append(stacked(jax.random.split(ks[-1], ecfg.num_layers + 1),
                            ecfg))
        got.append(params["encoder"]["blocks"])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
