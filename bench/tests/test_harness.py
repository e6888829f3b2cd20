"""The whole harness at a tiny size on the CPU: set-up, the served path
under load, the metrics, and the reference replay that decides
``correct``.  Then the same run with the timed path broken underneath
(a committed token altered, a step that returns its state unchanged,
the dual cache never refreshed, answers swapped between the rows of a
batch), which has to come out not
correct, and the float8 control, which has to fail the cell's limit.

The chip is not looked for: the runs get the CPU device directly.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from repro.configs import get_config

SPEC = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
CELLS = {w["name"]: w for w in SPEC["workloads"]}
# the serving cell planned next (PERF.md, Open questions): Poisson
# arrivals served with a dual cache, which no cell of BENCHMARK.json runs
# yet, so the generator's open loop and the reference's dual-cache path
# run here, on the first cell's configuration and limits
SERVE = "llada8b-prob-dual-serve"
SERVE_MIX = {"arrivals": "poisson", "rate_per_s": 2.0,
             "prompt_lengths": {"128": 0.7, "512": 0.3},
             "decode": {"strategy": "probability", "cache_policy": "dual",
                        "gen_length": 128, "block_size": 32, "steps": 128},
             "max_batch": 8, "check_requests": 3, "trace_seconds": 6}
FIRST = SPEC["workloads"][0]["name"]


def cell_files(cell: str):
    """(config, mix, limits) of a cell, found by name."""
    entry = CELLS[FIRST if cell == SERVE else cell]
    config, mix, limits = (
        run._json(os.path.join(run.BENCH, kind, name + ".json"))
        for kind, name in (("configs", entry["config"]),
                           ("traffic", entry["traffic"]),
                           ("limits", entry["name"])))
    return config, SERVE_MIX if cell == SERVE else mix, limits


def tiny_config(config: dict) -> dict:
    """A configuration at the program's tiny preset of the same model:
    two layers and the family's widths of the preset."""
    t = get_config(config["repo_config"] + "-tiny")
    family = run.load_family(config["family"])
    return dict(config, repo_config=t.name, depth=2,
                sizes=family.tiny_sizes(config["sizes"], t))


def tiny_mix(mix: dict) -> dict:
    """A mix cut to a few short requests."""
    mix = json.loads(json.dumps(mix))
    mix["decode"].update(gen_length=16, block_size=8,
                         steps=mix["decode"]["steps"] // 8)
    lengths = sorted(int(x) for x in mix["prompt_lengths"])
    mix["prompt_lengths"] = dict(zip(
        [str(16 + 8 * i) for i in range(len(lengths))],
        mix["prompt_lengths"].values()))
    mix.update(max_batch=2, backlog=4, rate_per_s=3.0)
    return mix


def tiny(cell: str):
    """The cell's configuration and mix cut to the repo's tiny preset of
    the same model and a few short requests."""
    config, mix, limits = cell_files(cell)
    return tiny_config(config), tiny_mix(mix), limits


def mix_of(cell: str) -> dict:
    return cell_files(cell)[1]


def run_tiny(cell, seed=2**31 + 77, control=False):
    config, mix, limits = tiny(cell)
    return run.run_cell(SPEC, cell, config, mix, limits, seed, 4.0, False,
                        jax.devices(), control=control)


@pytest.mark.parametrize("cell", sorted(CELLS) + [SERVE])
def test_tiny_cell_is_correct(cell):
    res = run_tiny(cell)
    diag = res["diagnostics"]
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0, diag
    assert not any(diag["compiles_in_window"].values()), diag
    want = {"setup_s", "gen_tok_s"} if mix_of(cell)["arrivals"] == "backlog" \
        else {"setup_s", "ttfb_p90_s", "latency_p90_s"}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values()), \
        (res["metrics"], diag)
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell", sorted(CELLS) + [SERVE])
def test_control_reads_above_the_program(cell):
    """At this size the program computes in float32 on the CPU and
    serves the reference's own argmax; the float8 control, read at the
    same positions, reads a larger share and a wider gap and fails the
    cell's limits (at the cell's size, ``bench.calibrate`` reads it on
    the chip)."""
    config, mix, limits = tiny(cell)
    mix["check_requests"] = 4
    res = run.run_cell(SPEC, cell, config, mix, limits, 2**31 + 78, 3.0,
                       False, jax.devices(), control=True)
    got, ctrl = res["compared"], res["control"]
    assert res["correct"] and not ctrl["correct"], ctrl
    assert got["replayed_requests"]["value"] == 4
    assert ctrl["compared"]["mismatch_share"]["value"] > \
        got["mismatch_share"]["value"]
    diag = res["diagnostics"]
    assert diag["control_widest_gap"] > diag["widest_gap"]


def _alter_commits(monkeypatch, vocab: int):
    """Every token committed in an even canvas column is replaced by its
    successor where the decode step produces it."""
    import jax.numpy as jnp
    from repro.core import fdm, fdm_a, strategies
    orig = strategies.commit_topn

    def altered(x, conf, cand, eligible, n):
        out = orig(x, conf, cand, eligible, n)
        col = jnp.arange(x.shape[1])[None, :]
        bad = (out != x) & (col % 2 == 0)
        return jnp.where(bad, (out + 1) % vocab, out)

    for mod in (strategies, fdm_a, fdm):
        monkeypatch.setattr(mod, "commit_topn", altered)


@pytest.mark.parametrize("cell", [FIRST, SERVE])
def test_altered_token_is_not_correct(monkeypatch, cell):
    _alter_commits(monkeypatch, tiny(cell)[0]["sizes"]["vocab_size"])
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["compared"]["mismatch_share"]["value"] > \
        res["compared"]["mismatch_share"]["limit"]


@pytest.mark.parametrize("cell", sorted(CELLS) + [SERVE])
def test_step_returning_its_state_is_not_correct(monkeypatch, cell):
    """Every decode step returns the canvas it was given: nothing is
    committed and the answers stay masked."""
    from repro.core import fdm, fdm_a, strategies

    def unchanged(x, conf, cand, eligible, n):
        return x

    for mod in (strategies, fdm_a, fdm):
        monkeypatch.setattr(mod, "commit_topn", unchanged)
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["compared"]["unserved_tokens"]["value"] > \
        res["compared"]["unserved_tokens"]["limit"]


def test_stale_dual_cache_is_not_correct(monkeypatch):
    """The block refresh returns the prefill's cache: committed blocks
    are never seen by later blocks' cached context."""
    from repro.models import model
    orig = model.capture_cache
    gen = tiny(SERVE)[1]["decode"]["gen_length"]

    def stale(params, tokens, cfg, enc_out=None):
        tokens = tokens.at[:, -gen:].set(cfg.mask_token_id)
        return orig(params, tokens, cfg, enc_out)

    monkeypatch.setattr(model, "capture_cache", stale)
    res = run_tiny(SERVE)
    assert not res["correct"]


def test_rows_of_a_batch_swapped_is_not_correct(monkeypatch):
    """Half the batch gets the other half's answers."""
    from repro.serving.engine import ServingEngine
    orig = ServingEngine._finish_batch

    def swapped(self, batch, out, stats):
        out = np.asarray(jax.device_get(out))
        n = len(batch.requests)
        if n > 1:
            out = out.copy()
            out[:n] = out[np.roll(np.arange(n), 1)]
        return orig(self, batch, out, stats)

    monkeypatch.setattr(ServingEngine, "_finish_batch", swapped)
    res = run_tiny(FIRST)
    assert not res["correct"]
