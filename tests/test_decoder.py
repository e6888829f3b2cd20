"""The first-class Decoder / Strategy API (core/decoder.py,
core/strategies.py): registry round-trip with a custom carry-ful strategy,
cross-call runner-cache hits and weak eviction, and per-block streaming
callbacks — under every cache policy."""
import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DecodeConfig, get_config
from repro.core import (Decoder, Strategy, available_strategies,
                        commit_topn, decode_cache_info, decode_cache_scope,
                        register_strategy, reset_decode_cache_stats,
                        resolve_strategy, score_logits, unregister_strategy)
from repro.core.decoder import RunnerCache
from repro.models.model import forward, init_model

CFG = get_config("llada-8b").reduced()


@pytest.fixture(scope="module")
def model():
    params = init_model(jax.random.PRNGKey(0), CFG)
    model_fn = jax.jit(lambda x: forward(params, x, CFG)[0])
    return params, model_fn


def _dcfg(**over):
    base = dict(gen_length=16, block_size=8, steps=16, k=2, k1=2,
                strategy="probability")
    base.update(over)
    return DecodeConfig(**base)


# --------------------------------------------------------------------------
# registry round-trip: a custom strategy decodes end-to-end, no core edits
# --------------------------------------------------------------------------

class AlternatingStrategy(Strategy):
    """Toy carry-ful strategy: alternates between committing 1 and 2
    tokens per step (the carry is a device step counter), exercising both
    init_carry threading and fused/host parity for out-of-tree code."""

    name = "alternating"

    def init_carry(self, cfg, dcfg):
        return jnp.zeros((), jnp.int32)

    def step(self, rng, carry, x, active, model_fn, cfg, dcfg, n):
        logits = model_fn(x)
        s = score_logits(logits)
        take = jnp.where(carry % 2 == 0, 1, 2)
        new_x = commit_topn(x, s.max_prob, s.argmax, active,
                            jnp.full((x.shape[0],), take))
        return new_x, carry + 1, 1


@pytest.fixture()
def alternating():
    register_strategy(AlternatingStrategy(), replace=True)
    yield
    unregister_strategy("alternating")


def test_custom_strategy_registry_roundtrip(model, alternating):
    assert "alternating" in available_strategies()
    assert resolve_strategy("alternating").name == "alternating"
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    dcfg = _dcfg(strategy="alternating")
    dec = Decoder(params, CFG, dcfg)
    out_f, s_f = dec.generate(jax.random.PRNGKey(0), prompts)
    assert out_f.shape == (2, 22)
    assert not (np.asarray(out_f) == CFG.mask_token_id).any()
    # the carry made commit widths alternate 1,2,1,2… -> fewer steps than
    # the 16 a 1-per-step strategy needs, more than the 8 of 2-per-step
    assert 8 < s_f.steps < 16
    # fused/host parity holds for out-of-tree strategies too
    out_h, s_h = Decoder(params, CFG,
                         dataclasses.replace(dcfg, fused_loop=False)
                         ).generate(jax.random.PRNGKey(0), prompts)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_h))
    assert s_f.steps == s_h.steps


def test_custom_strategy_carry_survives_cached_path(model, alternating):
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    dec = Decoder(params, CFG, _dcfg(strategy="alternating",
                                     cache_policy="prefix"))
    out, _ = dec.generate(jax.random.PRNGKey(0), prompts)
    assert not (np.asarray(out) == CFG.mask_token_id).any()


def test_register_strategy_rejects_duplicates(alternating):
    with pytest.raises(ValueError):
        register_strategy(AlternatingStrategy())
    with pytest.raises(KeyError):
        resolve_strategy("definitely-not-registered")


def test_generate_rejects_unknown_extras(model):
    params, _ = model
    with pytest.raises(TypeError, match="unexpected keyword"):
        Decoder(params, CFG, _dcfg()).generate(
            jax.random.PRNGKey(0), jnp.full((1, 4), 2, jnp.int32),
            on_block_comitted=lambda *a: None)      # the typo'd spelling


# --------------------------------------------------------------------------
# cross-call cache: zero recompiles on repeat, weak eviction on GC
# --------------------------------------------------------------------------

def test_cross_call_cache_zero_recompiles(model):
    """A second decode with the same params — even through a *new*
    Decoder — must neither build nor trace anything, in both the plain
    and KV-cached paths.  Runs against a scoped fresh cache so the
    counter assertions can't flake on test ordering (the process-wide
    counters see every other test's decodes)."""
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    cached_dcfg = _dcfg(cache_policy="prefix")
    with decode_cache_scope():
        d1 = Decoder(params, CFG, _dcfg())
        d1.generate(jax.random.PRNGKey(0), prompts)
        Decoder(params, CFG, cached_dcfg).generate(jax.random.PRNGKey(0),
                                                   prompts)
        before = decode_cache_info()
        d2 = Decoder(params, CFG, _dcfg())      # fresh but equal config
        d2.generate(jax.random.PRNGKey(1), prompts)
        Decoder(params, CFG, cached_dcfg).generate(jax.random.PRNGKey(1),
                                                   prompts)
        after = decode_cache_info()
        assert after.traces == before.traces, "recompiled on repeat decode"
        assert after.misses == before.misses, "rebuilt a cached runner"
        assert after.hits > before.hits


def test_cache_stats_reset_keeps_runners(model):
    """reset_decode_cache_stats zeroes the counters without dropping
    compiled runners: the next identical decode is all hits, zero
    misses/traces — the hermetic baseline compile-count tests need."""
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    with decode_cache_scope():
        Decoder(params, CFG, _dcfg()).generate(jax.random.PRNGKey(0),
                                               prompts)
        assert decode_cache_info().misses > 0
        reset_decode_cache_stats()
        zeroed = decode_cache_info()
        assert (zeroed.hits, zeroed.misses, zeroed.traces) == (0, 0, 0)
        assert zeroed.runners > 0, "reset must not drop compiled runners"
        Decoder(params, CFG, _dcfg()).generate(jax.random.PRNGKey(1),
                                               prompts)
        after = decode_cache_info()
        assert after.misses == 0 and after.traces == 0
        assert after.hits > 0


def test_cache_scope_restores_previous_cache(model):
    params, _ = model
    prompts = jnp.full((1, 4), 2, jnp.int32)
    outer = decode_cache_info()
    with decode_cache_scope() as scoped:
        Decoder(params, CFG, _dcfg(gen_length=8, block_size=8,
                                   steps=8)).generate(
            jax.random.PRNGKey(0), prompts)
        assert scoped.info().misses > 0
    # the scope's work never touched the process-wide counters
    assert decode_cache_info() == outer


def test_cache_entry_evicted_when_params_dropped():
    """New params after GC must not leak the old entry: the cache keys
    weakly on the weights' identity and runners never bake them in."""
    cache = RunnerCache()                      # private cache: no
    prompts = jnp.full((1, 4), 2, jnp.int32)   # interference from fixtures
    dcfg = _dcfg(gen_length=8, block_size=8, steps=8)
    p1 = init_model(jax.random.PRNGKey(1), CFG)
    Decoder(p1, CFG, dcfg, cache=cache).generate(jax.random.PRNGKey(0),
                                                 prompts)
    assert cache.info().entries == 1
    del p1
    gc.collect()
    assert cache.info().entries == 0, "dropped params still cached"
    p2 = init_model(jax.random.PRNGKey(2), CFG)
    Decoder(p2, CFG, dcfg, cache=cache).generate(jax.random.PRNGKey(0),
                                                 prompts)
    assert cache.info().entries == 1


def test_cache_evicts_when_any_leaf_dropped():
    """Eviction must anchor on EVERY params leaf, not just the first: the
    key is a tuple of leaf ids, which are only unique while the leaves
    are alive — if a non-first leaf dies (partial weight swap) while leaf
    0 survives, a recycled id could alias a stale entry into a false
    cache hit.  First finalizer wins."""
    cache = RunnerCache()
    prompts = jnp.full((1, 4), 2, jnp.int32)
    dcfg = _dcfg(gen_length=8, block_size=8, steps=8)
    p1 = init_model(jax.random.PRNGKey(1), CFG)
    leaf0 = jax.tree.leaves(p1)[0]    # noqa: F841 — held alive on purpose
    assert len(jax.tree.leaves(p1)) > 1, "test needs a multi-leaf pytree"
    Decoder(p1, CFG, dcfg, cache=cache).generate(jax.random.PRNGKey(0),
                                                 prompts)
    assert cache.info().entries == 1
    del p1                       # every leaf except leaf0 dies ...
    gc.collect()
    assert cache.info().entries == 0, \
        "non-first leaf died but the entry survived"
    del leaf0                    # ... and the stale finalizers are
    gc.collect()                 # detached: leaf0's can't double-evict
    assert cache.info().entries == 0


def test_cache_evicts_model_fn_entries_too(model):
    params, _ = model
    cache = RunnerCache()
    prompts = jnp.full((1, 4), 2, jnp.int32)
    dcfg = _dcfg(gen_length=8, block_size=8, steps=8)
    mf = jax.jit(lambda x: forward(params, x, CFG)[0])
    Decoder(mf, CFG, dcfg, cache=cache).generate(jax.random.PRNGKey(0),
                                                 prompts)
    assert cache.info().entries == 1
    del mf
    gc.collect()
    assert cache.info().entries == 0


# --------------------------------------------------------------------------
# streaming: on_block_committed fires once per block, in order, under all
# three drivers (host / per-block fused / whole-request io_callback)
# --------------------------------------------------------------------------

DRIVERS = {
    "host": dict(fused_loop=False),
    "block": dict(fused_loop=True, fused_blocks=False),
    "request": dict(fused_loop=True, fused_blocks=True),
}


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_on_block_committed_ordering(model, driver):
    """Exactly num_blocks events, in block order, with the right (lo, hi)
    — including the whole-request driver, where the callback arrives via
    an ordered io_callback from inside the single compiled dispatch."""
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    events = []
    dec = Decoder(params, CFG, _dcfg(gen_length=16, block_size=4,
                                     **DRIVERS[driver]))
    out, _ = dec.generate(
        jax.random.PRNGKey(0), prompts,
        on_block_committed=lambda blk, lo, hi, x: events.append(
            (blk, lo, hi, bool((np.asarray(x)[:, lo:hi]
                                != CFG.mask_token_id).all()))))
    assert [(e[0], e[1], e[2]) for e in events] == \
        [(0, 6, 10), (1, 10, 14), (2, 14, 18), (3, 18, 22)]
    # at each event the just-committed block is fully decoded
    assert all(e[3] for e in events)


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_on_block_committed_cached_path(model, driver):
    """The streaming contract is policy-independent: the KV-cached path
    delivers the same num_blocks ordered events with correct bounds under
    every driver (the whole-request driver folds the refreshes into the
    same dispatch the io_callbacks fire from)."""
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    events = []
    dec = Decoder(params, CFG, _dcfg(cache_policy="prefix",
                                     **DRIVERS[driver]))
    dec.generate(jax.random.PRNGKey(0), prompts,
                 on_block_committed=lambda blk, lo, hi, x:
                 events.append((blk, lo, hi)))
    assert events == [(0, 6, 14), (1, 14, 22)]


def test_streaming_and_plain_request_decodes_match(model):
    """The streaming whole-request variant (its own compiled program, with
    io_callbacks woven in) must not perturb the decode itself."""
    params, _ = model
    prompts = jnp.full((2, 6), 2, jnp.int32)
    dec = Decoder(params, CFG, _dcfg())
    out_plain, s_plain = dec.generate(jax.random.PRNGKey(0), prompts)
    out_stream, s_stream = dec.generate(jax.random.PRNGKey(0), prompts,
                                        on_block_committed=lambda *a: None)
    np.testing.assert_array_equal(np.asarray(out_plain),
                                  np.asarray(out_stream))
    assert s_plain.steps == s_stream.steps


def test_model_fn_decoder_rejects_cache_policy(model):
    _, model_fn = model
    with pytest.raises(ValueError, match="params"):
        Decoder(model_fn, CFG, _dcfg(cache_policy="prefix")).generate(
            jax.random.PRNGKey(0), jnp.full((1, 4), 2, jnp.int32))


# --------------------------------------------------------------------------
# lower_blocks: the served per-block programs, lowered ahead of time
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,policy", [("fdm_a", "none"),
                                             ("fdm", "none"),
                                             ("fdm_a", "prefix")])
def test_lower_blocks_is_the_served_program(model, strategy, policy):
    """``lower_blocks`` lowers the exact runners ``generate_blocks``
    dispatches: compiling it and then decoding builds no new runner, and
    the compiled block program decodes the first block like the served
    path does."""
    params, _ = model
    dcfg = _dcfg(strategy=strategy, cache_policy=policy)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                CFG.vocab_size - 1)
    with decode_cache_scope() as cache:
        dec = Decoder(params, CFG, dcfg)
        lowered = dec.lower_blocks(2, 8)
        assert set(lowered) == ({"block"} if policy == "none"
                                else {"block", "refresh"})
        compiled = {k: v.compile() for k, v in lowered.items()}
        for c in compiled.values():
            assert c.memory_analysis() is not None
        built = cache.info().misses
        blocks = dec.generate_blocks(jax.random.PRNGKey(5), prompt)
        first = next(blocks)
        for _ in blocks:
            pass
        assert cache.info().misses == built

    from repro.core.masking import fully_masked
    strat = resolve_strategy(strategy)
    _, _, _, sched = dec._geometry()
    x = fully_masked(CFG, prompt, dcfg.gen_length)
    args = (x, jax.random.PRNGKey(5), jnp.int32(8), jnp.asarray(sched[0]),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
            strat.init_carry_shaped(CFG, dcfg, 2, x.shape[1]))
    # each runner returns its outputs and the expert counters (None for
    # a model without experts)
    if policy == "none":
        out, _ = compiled["block"](params, {}, *args)
    else:
        state, _ = compiled["refresh"](params, x)
        out, _ = compiled["block"](params, *args, state)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(first.x))

    # shapes alone lower the same programs, with nothing on the device
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          params)
    with decode_cache_scope():
        abstract = Decoder(shapes, CFG, dcfg).lower_blocks(2, 8)
    assert {k: v.as_text() for k, v in abstract.items()} == \
        {k: v.as_text() for k, v in lowered.items()}


def test_lower_blocks_refuses_the_host_loop(model):
    """A decode the server runs on the host step loop has no block
    program: ``lower_blocks`` says so instead of lowering another one."""
    params, _ = model
    with pytest.raises(ValueError, match="host step loop"):
        Decoder(params, CFG, _dcfg(fused_loop=False)).lower_blocks(2, 8)
