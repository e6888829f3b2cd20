"""BENCHMARK.json against its files: every cell names a configuration, a
traffic mix and limits that parse, every per-layer metric has a reader,
and the names and units keep to the benchmark's rules."""
import json
import os
import re

import pytest

from bench import run, traffic

ROOT = run.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_parse(cell):
    spec, entry, config, mix, limits = run.load_cell(cell)
    assert entry["chips"] == 1
    assert config["name"] == entry["config"]
    family = run.load_family(config["family"])
    assert "mask_token_id" in config["sizes"]
    assert mix["arrivals"] in ("backlog", "poisson")
    assert abs(sum(mix["prompt_lengths"].values()) - 1.0) < 1e-9
    assert mix["decode"]["gen_length"] % mix["decode"]["block_size"] == 0
    assert 0 < limits["mismatch_share"] < 1
    # the configuration's program exists with the configured shapes
    cfg = family.program_config(config)
    assert cfg.num_layers == config["depth"]
    assert family.param_shapes(config["sizes"], config["depth"])


def test_every_per_layer_metric_has_a_reader():
    cells = {w["name"] for w in SPEC["workloads"]}
    for entry in SPEC["per_layer"]:
        path = os.path.join(ROOT, "bench", "metrics", entry["name"] + ".py")
        assert os.path.isfile(path), path
        assert set(entry["workloads"]) <= cells
    for cell in cells:
        readers = run.per_layer_readers(SPEC, cell)
        assert readers and all(callable(r) for _, r in readers.values())


def test_names_units_and_bounds():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200


def test_traffic_is_the_same_work_for_every_seed():
    """Two seeds: the same due times and prompt lengths in the same
    order, other token ids."""
    mix = {"arrivals": "poisson", "rate_per_s": 2.0,
           "prompt_lengths": {"128": 0.7, "512": 0.3}}
    a = traffic.schedule(mix, 40.0, 1, 100)
    b = traffic.schedule(mix, 40.0, 2**31 + 5, 100)
    assert len(a) == round(mix["rate_per_s"] * 40.0)
    assert [(t, len(p)) for t, p in a] == [(t, len(p)) for t, p in b]
    assert a[0][0] == 0.0 and max(t for t, _ in a) < 40.0
    assert sorted({len(p) for _, p in a}) == [128, 512]
    assert [p.tolist() for _, p in a] != [p.tolist() for _, p in b]


def test_end_to_end_arithmetic():
    """gen_tok_s by hand: two requests of one batch get blocks 0 and 1,
    the next batch's block 0 follows; the window [1.5, 2.5] holds half
    of each interval between completions."""
    from types import SimpleNamespace
    recs = [SimpleNamespace(blocks=[(1.0, 0), (2.0, 1)]),
            SimpleNamespace(blocks=[(1.001, 0), (2.001, 1)]),
            SimpleNamespace(blocks=[(3.0, 0)]),
            SimpleNamespace(blocks=[(3.002, 0)])]
    assert [len(g) for g in run.completions(recs)] == [2, 2, 2]
    assert run.gen_tok_s(recs, 1.5, 2.5, 32) == 32 * 2 * 0.5 * 2
    assert run.block_intervals(recs, 1.5, 2.5) == [1.0, 1.0]
    assert run.percentile(range(1, 11), 0.9) == 9
    assert run.percentile([1.0, float("inf")], 0.9) == float("inf")
