"""A second model family comes in as files alone: a configuration and a
family module for the program's ``qwen3-14b``, whose per-head q/k
RMSNorm scales are leaves the dense family lacks, written to a temporary
directory that the harness is pointed at.  At the tiny preset its cell
runs end to end (program check, layout check, seeded weights, the served
path, the replay) and comes out correct; a copy of the family whose
reference leaves the q/k norm out comes out not correct."""
import glob
import json
import os

import jax
import pytest

from bench import run
from bench.tests.test_harness import (FIRST, SPEC, cell_files,
                                     tiny_config, tiny_mix)

FAMILY = "qk_gqa"
SOURCE = '''"""Dense GQA with per-head q/k RMSNorm (Qwen3): the dense family with
two more leaves a layer, the scales of the query and key norms."""
from bench.families import dense_gqa as dense
from bench.weights import Leaf

NORM_QK = True
KINDS = dict(dense.KINDS, qk_norm=True)
tiny_sizes = dense.tiny_sizes
layer_flops, head_flops = dense.layer_flops, dense.head_flops
keys, refresh_flops = dense.keys, dense.refresh_flops


def program_config(config):
    return dense.check_program(config, KINDS)


def param_shapes(sizes, depth):
    """The norms' scales drawn, not ones: a served checkpoint's are
    learned, and at ones the norm all but keeps random projections as
    they are."""
    hd = sizes["head_dim"]
    return dict(dense.param_shapes(sizes, depth),
                **{"blocks/attn/q_scale": Leaf((depth, hd), 1.0),
                   "blocks/attn/k_scale": Leaf((depth, hd), 1.0)})


def qkv(lp, h, pos, dm, mm_):
    b, l, _ = h.shape
    q = mm_(h, lp["attn/wq"]).reshape(b, l, dm.nq, dm.hd)
    k = mm_(h, lp["attn/wk"]).reshape(b, l, dm.nkv, dm.hd)
    if NORM_QK:
        q = dense.rms(q, lp["attn/q_scale"], dm.eps)
        k = dense.rms(k, lp["attn/k_scale"], dm.eps)
    v = mm_(h, lp["attn/wv"]).reshape(b, l, dm.nkv, dm.hd)
    return dense.rope(q, pos, dm), dense.rope(k, pos, dm), v


forward_rows, capture, forward_window = dense.reference(dense.block(qkv))
'''
CONFIG = {"name": "qwen3-14b", "family": FAMILY, "repo_config": "qwen3-14b",
          "sizes": {"d_model": 5120, "num_heads": 40, "num_kv_heads": 8,
                    "head_dim": 128, "d_ff": 17408, "vocab_size": 151936,
                    "rope": "standard", "rope_theta": 1000000.0,
                    "norm_eps": 1e-06, "mask_token_id": 151935},
          "depth": 40, "weights_dtype": "bfloat16"}


def run_family(tmp_path, monkeypatch, source: str):
    """The first cell's tiny mix on the qk-norm configuration, with the
    harness's configuration and family directories in ``tmp_path``."""
    for sub, name, text in (("configs", CONFIG["name"] + ".json",
                             json.dumps(CONFIG)),
                            ("families", FAMILY + ".py", source)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / name).write_text(text)
    monkeypatch.setattr(run, "CONFIGS", str(tmp_path / "configs"))
    monkeypatch.setattr(run, "FAMILIES", str(tmp_path / "families"))
    config = tiny_config(run.load_config(CONFIG["name"]))
    _, mix, limits = cell_files(FIRST)
    return run.run_cell(SPEC, FIRST, config, tiny_mix(mix), limits,
                        2**31 + 91, 4.0, False, jax.devices())


def test_family_added_as_files_is_correct(tmp_path, monkeypatch):
    res = run_family(tmp_path, monkeypatch, SOURCE)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"]["replayed_requests"]["value"] > 0


def test_family_without_its_qk_norm_is_not_correct(tmp_path, monkeypatch):
    broken = SOURCE.replace("NORM_QK = True", "NORM_QK = False")
    assert broken != SOURCE
    res = run_family(tmp_path, monkeypatch, broken)
    assert not res["correct"]
    assert res["compared"]["mismatch_share"]["value"] > \
        res["compared"]["mismatch_share"]["limit"]


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(run.BENCH, "*.py"))
    + glob.glob(os.path.join(run.BENCH, "families", "*.py"))))
def test_no_shared_module_names_the_family(path):
    with open(path) as f:
        assert FAMILY not in f.read()
