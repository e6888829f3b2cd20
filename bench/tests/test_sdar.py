"""The SDAR-30B-A3B cell's own pieces: its family as files, its count,
and its expert-roofline reader.

The family (``moe_block``) comes in the way ``test_families.py`` brings
a second one: its configuration and module copied to a temporary
directory that the harness is pointed at.  The cell's own mix, cut to
the tiny preset, comes out correct; a copy whose reference drops the
block-causal mask, and one that adds every routed expert's part where
the program holds a share, do not."""
import json
import os
from types import SimpleNamespace

import jax
import pytest

from bench import flops, run
from bench.tests.test_harness import SPEC, cell_files, tiny_config, tiny_mix

CELL = "sdar30b-fdma-dual-offline"


@pytest.mark.parametrize("flag,correct", [
    (None, True), ("BLOCK_CAUSAL", False), ("HELD_SHARE", False)],
    ids=["as-is", "without-the-mask", "without-the-share"])
def test_moe_block_family_as_files(tmp_path, monkeypatch, flag, correct):
    with open(os.path.join(run.FAMILIES, "moe_block.py")) as f:
        source = f.read()
    if flag:
        broken = source.replace(f"{flag} = True", f"{flag} = False")
        assert broken != source
        source = broken
    config, mix, limits = cell_files(CELL)
    for sub, name, text in (("configs", "sdar-30b-a3b.json",
                             json.dumps(config)),
                            ("families", "moe_block.py", source)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / name).write_text(text)
    monkeypatch.setattr(run, "CONFIGS", str(tmp_path / "configs"))
    monkeypatch.setattr(run, "FAMILIES", str(tmp_path / "families"))
    res = run.run_cell(SPEC, CELL,
                       tiny_config(run.load_config("sdar-30b-a3b")),
                       tiny_mix(mix), limits, 2**31 + 93, 4.0, False,
                       jax.devices())
    assert res["correct"] is correct, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if not correct:
        assert res["compared"]["mismatch_share"]["value"] > \
            res["compared"]["mismatch_share"]["limit"]


def _sdar():
    return run.load_family("moe_block"), \
        run.load_config("sdar-30b-a3b")["sizes"]


def test_sdar_layer_by_hand():
    # 4 window rows at 128..131 see the keys up to 132 (block-causal, block
    # 4); q, k, v, o with GQA 32/4 of 128; the router over 128 experts; and
    # 6 * 2048 * 768 per (row, held expert) pair, 4 * 8 * 16/128 pairs
    fam, s = _sdar()
    assert fam.keys(s, 128, 4, 256) == 132
    assert fam.keys(s, 0, 256, 256) == 130          # the mean over 0..255
    want = 2 * 4 * 2048 * (32 + 8) * 128 + 2 * 4 * 32 * 128 * 2048 \
        + 2 * 2 * 4 * 132 * 32 * 128 + 2 * 4 * 2048 * 128 \
        + 6 * 2048 * 768 * 4
    assert fam.layer_flops(s, 4, 132) == want == 199_491_584


def test_sdar_cell_count_as_pinned():
    """The SDAR cell at depth 48: 32 blocks of 4, one commit a step; a
    refresh is one block through every layer, a step one window forward.
    It never exceeds what the full-canvas forward would cost."""
    fam, s = _sdar()
    geo = {"gen_length": 128, "block_size": 4, "steps": 128,
           "cache_policy": "dual"}
    dual = flops.request_flops(fam, s, 48, geo, 128, 3.0)
    # by hand: block b at lo = 128 + 4b refreshes the block before it
    # (rows lo-4..lo-1, keys up to lo), then four window forwards (keys
    # up to lo + 4) whose head reads 4, 3, 2 and 1 masked rows
    by_hand = sum(48 * fam.layer_flops(s, 4, lo)
                  + 4 * 48 * fam.layer_flops(s, 4, lo + 4)
                  + fam.head_flops(s, 4 + 3 + 2 + 1)
                  for lo in range(128, 256, 4))
    assert fam.refresh_flops(s, 48, 132, 4, 256) == \
        48 * fam.layer_flops(s, 4, 132)
    assert dual == by_hand == 1762043887616.0
    for fwd in (1.0, 3.0):
        full = flops.request_flops(fam, s, 48, dict(geo, cache_policy="none"),
                                   128, fwd)
        assert dual < full


def test_expert_roofline_by_hand():
    """``expert_roofline.offline`` on three grouped-matmul ops (one layer
    call), each after the fusion that staged its expert weights, and an
    op in between that is not an operand of the next kernel."""
    name = "expert_roofline.offline"
    read = run._module(os.path.join(run.BENCH, "metrics", name + ".py"),
                       "bench_metric_expert_roofline")
    ops, t = [], 0.0
    for i, (out, w) in enumerate((("512,768", "16,2048,768"),
                                  ("512,768", "16,2048,768"),
                                  ("512,2048", "16,768,2048"))):
        stage = f"%dynamic-slice_bitcast_fusion.{i}"
        ops.append((f"{stage} = bf16[{w}]{{2,1,0}} fusion(bf16[48,{w}])",
                    t, 60e-6, ""))
        ops.append((f"%fusion.{i} = f32[8]{{0}} fusion(f32[8] %p)",
                    t + 60e-6, 5e-6, ""))
        ops.append((f"%gmm.{i} = bf16[{out}]{{1,0}} custom-call(s32[] %m, "
                    f"bf16[512,2048]{{1,0}} %x, bf16[{w}]{{2,1,0}} {stage}), "
                    f"custom_call_target=\"tpu_custom_call\"",
                    t + 65e-6, 40e-6, ""))
        t += 200e-6
    rec = SimpleNamespace(ok=True, stats={"expert_rows": 640.0,
                                          "experts_read": 160.0,
                                          "expert_calls": 10.0})
    view = SimpleNamespace(
        records=[rec], trace={"ops": ops},
        config={"sizes": {"d_model": 2048, "moe_d_ff": 768},
                "weights_dtype": "bfloat16"},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    # 64 rows and 16 experts a call: bytes bound it
    least = (16 * 3 * 2048 * 768 + 2 * 64 * 2048) * 2 / 819e9
    assert 6 * 2048 * 768 * 64 / 197e12 < least
    assert read.read(view) == pytest.approx(100 * least / 300e-6)
    # a program without the counters reads nothing
    rec.stats = {"forward_equivalents": 3.0}
    assert read.read(view) is None
