"""SDAR-30B-A3B's two mechanisms on the program's normal path, at its
``-tiny`` preset on the CPU, against the plain reference of
``bench/families/moe_block.py`` on seeded float32 weights: the full
forward and the dual cache (which the block-causal mask makes exact),
the dropless expert layer that holds a share of the experts, and the
entry check that keeps a block-causal model in its own blocks.

Tolerances: program and reference both compute in float32 here, the
reference at ``Precision.HIGHEST`` and XLA:CPU's float32 matmuls at full
float32, so they differ only in the order of their sums (relative
rounding near 1e-6, over logits and activations of order 1); 1e-4 holds
that with room and is three orders below a bfloat16 rounding.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.families import moe_block as ref
from repro.configs import DecodeConfig, get_config
from repro.core import Decoder
from repro.models import moe
from repro.models.attention import position_mask
from repro.models.model import (capture_cache, forward, forward_cached,
                                init_model)

TINY = get_config("sdar-30b-a3b-tiny")
BLK = TINY.attn_block
SEED = 2**31 + 17
TOL = dict(rtol=1e-4, atol=1e-4)       # float32 sum order (module doc)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes(first=0, held=None) -> dict:
    m = TINY.moe
    return {"d_model": TINY.d_model, "num_heads": TINY.num_heads,
            "num_kv_heads": TINY.num_kv_heads, "head_dim": TINY.head_dim,
            "vocab_size": TINY.vocab_size, "num_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "norm_topk_prob": True, "moe_d_ff": m.moe_d_ff,
            "experts_first": first, "experts_held": held or m.num_experts,
            "block_length": BLK, "rope": "standard",
            "rope_theta": TINY.rope_theta, "norm_eps": 1e-6,
            "mask_token_id": TINY.mask_token_id}


def config(s: dict):
    return dataclasses.replace(TINY, moe=dataclasses.replace(
        TINY.moe, held_first=s["experts_first"],
        held_count=s["experts_held"]))


def seeded(s: dict, depth: int = 2):
    flat = weights.flatten(weights.make_params(
        ref.param_shapes(s, depth), SEED, jnp.float32))
    return flat, weights.unflatten(flat)


def canvas(batch=2, prompt=16, gen=16, seed=0):
    rs = np.random.RandomState(seed)
    toks = rs.randint(0, TINY.mask_token_id, (batch, prompt + gen))
    toks[:, prompt:] = TINY.mask_token_id
    return toks.astype(np.int32)


# the cell's share: 2 of the 16 experts, as 16 of 128
SHARE = sizes(held=2)


def test_full_forward_matches_the_reference():
    flat, params = seeded(SHARE)
    toks = canvas()
    got = forward(params, jnp.asarray(toks), config(SHARE))[0]
    want = ref.forward_rows(flat, jnp.asarray(toks), 0, SHARE, toks.shape[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_dual_cache_matches_the_reference_and_the_full_forward():
    """Capture at each block's start, then window steps: the program's
    logits equal the reference's, and — because a block sees only itself
    and the blocks before it — the full forward's rows of the block on
    the canvas the window sees, whatever the stale suffix holds."""
    flat, params = seeded(SHARE)
    cfg = config(SHARE)
    toks = canvas()
    rs = np.random.RandomState(1)
    for lo in (16, 16 + BLK):
        state = capture_cache(params, jnp.asarray(toks), cfg)
        kv = ref.capture(flat, jnp.asarray(toks), SHARE)
        for step in range(2):
            # commit a token of the window, as a step would
            toks[:, lo + step] = rs.randint(0, TINY.mask_token_id, 2)
            win = jnp.asarray(toks[:, lo:lo + BLK])
            got = forward_cached(params, win, jnp.int32(lo), state, cfg)
            want = ref.forward_window(flat, win, lo, kv, SHARE)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **TOL)
            later = toks.copy()
            later[:, lo + BLK:] = rs.randint(0, TINY.mask_token_id,
                                             later[:, lo + BLK:].shape)
            full = forward(params, jnp.asarray(later), cfg)[0]
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(full[:, lo:lo + BLK]),
                                       **TOL)


def test_one_expert_for_every_token_drops_nothing():
    """A router that sends every token to expert 0 (and to the next
    ones by its ties): the dropless layer computes every pair, where the
    capacity path keeps 128 of the 256 tokens at expert 0."""
    s = sizes()
    cfg = config(s)
    flat, _ = seeded(s)
    lp = {k[len("blocks/"):]: v[0] for k, v in flat.items()
          if k.startswith("blocks/moe/")}
    router = jnp.zeros_like(lp["moe/router"]).at[:, 0].set(1.0)
    lp["moe/router"] = router
    p = {k[len("moe/"):]: v for k, v in lp.items()}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (2, 128, TINY.d_model)))

    @jax.jit
    def layer(p, x):
        with moe.expert_counter(cfg) as counts:
            out, _ = moe.moe_forward(p, x, cfg)
            return out, counts()

    out, counts = layer(p, x)
    want = ref.experts(lp, x, ref.Dims.of(s), False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    k = TINY.moe.num_experts_per_tok
    assert counts.tolist() == [256 * k, k, 1]       # rows, experts, calls
    assert moe.capacity(256, cfg) < 256
    capped, _ = moe._dispatch(p, x.reshape(256, -1), cfg, 1.25, False)
    assert not np.allclose(np.asarray(capped).reshape(want.shape),
                           np.asarray(want), **TOL)


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Eight chips of two experts each: their parts of the layer's
    output, summed, are the output of the layer that holds all 16."""
    whole = sizes()
    flat, _ = seeded(whole)
    p = {k[len("blocks/moe/"):]: v[0] for k, v in flat.items()
         if k.startswith("blocks/moe/")}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, TINY.d_model))
    want, _ = moe.moe_forward(p, x, config(whole))
    parts = []
    for chip in range(8):
        share = sizes(first=2 * chip, held=2)
        mine = dict(p, **{w: p[w][2 * chip:2 * chip + 2]
                          for w in ("w_gate", "w_up", "w_down")})
        parts.append(moe.moe_forward(mine, x, config(share))[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               **TOL)
    assert not np.allclose(np.asarray(parts[0]), np.asarray(want), **TOL)


def test_a_bidirectional_config_builds_no_mask():
    assert position_mask(jnp.arange(8), jnp.arange(8)) is None
    llada = get_config("llada-8b-tiny")
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               llada))
    toks = jax.ShapeDtypeStruct((1, 32), jnp.int32)

    def text(cfg, p):
        return jax.jit(lambda p, t: forward(p, t, cfg)[0]).lower(p, toks) \
            .as_text()

    assert "-1.000000e+30" not in text(llada, params)
    _, sdar = seeded(SHARE)
    assert "-1.000000e+30" in text(config(SHARE), sdar)


def test_llada_logits_are_unchanged():
    """The dense bidirectional model's forward, cache capture and cached
    window step digest as they did before the block-causal mask and the
    expert layer came in (``tests/pinned_llada.py``, one CPU core)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "pinned_llada.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "forward": "ad6e4ffeb6da7fd2", "capture": "c0ad8422864362fa",
        "cached": "58cd8204cd134a62"}


# -- the entry check ----------------------------------------------------------

DCFG = DecodeConfig(gen_length=16, block_size=BLK, steps=16,
                    strategy="fdm_a", cache_policy="dual")


@pytest.mark.parametrize("block_size,prompt_len", [(2 * BLK, 16),
                                                   (BLK, 12)],
                         ids=["block-size", "prompt-length"])
def test_block_causal_geometry_is_refused(block_size, prompt_len):
    _, params = seeded(SHARE)
    dcfg = dataclasses.replace(DCFG, block_size=block_size)
    dec = Decoder(params, config(SHARE), dcfg)
    prompt = jnp.zeros((1, prompt_len), jnp.int32)
    with pytest.raises(ValueError, match="block-causally"):
        dec.generate(jax.random.PRNGKey(0), prompt)
    with pytest.raises(ValueError, match="block-causally"):
        dec.generate_blocks(jax.random.PRNGKey(0), prompt)


def test_block_causal_geometry_is_400_at_the_server():
    from repro.configs import RouterConfig, ServerConfig
    from repro.serving import (ModelRouter, ServerError, ServerThread,
                               ServingClient, ServingEngine)
    _, params = seeded(SHARE)
    router = ModelRouter(RouterConfig())
    router.register("sdar", lambda: ServingEngine(
        params, config(SHARE), DCFG, max_batch=2))
    handle = ServerThread(router, ServerConfig(port=0)).start()
    try:
        client = ServingClient(handle.host, handle.port)
        with pytest.raises(ServerError) as err:
            client.generate(list(range(16)), block_size=2 * BLK)
        assert err.value.status == 400 and "block-causally" in \
            err.value.message
        with pytest.raises(ServerError) as err:
            client.generate(list(range(12)))
        assert err.value.status == 400 and "block-causally" in \
            err.value.message
        res = client.generate(list(range(16)), wait=True)
        assert res["status"] == "ok"
        # the expert counters ride to the request's stats: 2 layers ×
        # (2 refreshes + 16 steps × 2 forwards of FDM-A's search)
        stats = res["stats"]
        assert stats["expert_calls"] == 2 * (2 + 16 * 2)
        assert 0 < stats["experts_read"] <= 2 * stats["expert_calls"]
        assert stats["expert_rows"] > 0
    finally:
        handle.stop()
