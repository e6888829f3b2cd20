"""bench/flops.py, through the dense GQA family's terms, against counts
worked out by hand."""
from bench import flops
from bench.families import dense_gqa as fam

LLADA = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 32,
         "head_dim": 128, "d_ff": 12288, "vocab_size": 126464}
CHATGLM = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 2,
           "head_dim": 128, "d_ff": 13696, "vocab_size": 65024}


def test_llada_layer_by_hand():
    # 256 rows over 256 keys: q,k,v,o 4 * 2*256*4096*4096, attention
    # 2 * 2*256*256*4096, MLP 3 * 2*256*4096*12288
    want = 4 * 2 * 256 * 4096 * 4096 + 2 * 2 * 256 * 256 * 4096 \
        + 3 * 2 * 256 * 4096 * 12288
    assert fam.layer_flops(LLADA, 256, 256) == want == 112_742_891_520


def test_chatglm_layer_by_hand():
    # GQA: k and v project to 2 groups of 128
    want = 2 * 512 * 4096 * (32 + 4) * 128 + 2 * 512 * 4096 * 4096 \
        + 2 * 2 * 512 * 512 * 4096 + 3 * 2 * 512 * 4096 * 13696
    assert fam.layer_flops(CHATGLM, 512, 512) == want


def test_head_counts_only_read_rows():
    assert fam.head_flops(LLADA, 32) == 2 * 32 * 4096 * 126464
    assert flops.forward_flops(fam, LLADA, 2, 256, 256, 32) == \
        2 * fam.layer_flops(LLADA, 256, 256) + fam.head_flops(LLADA, 32)


def test_request_none_one_token_per_step():
    # gen 64, block 32, 64 steps: each block's steps read 32, 31, ..., 1
    # masked rows of the block with the head
    geo = {"gen_length": 64, "block_size": 32, "steps": 64,
           "cache_policy": "none"}
    body = 64 * 2 * fam.layer_flops(LLADA, 128, 128)
    head = 2 * sum(fam.head_flops(LLADA, m) for m in range(1, 33))
    assert flops.request_flops(fam, LLADA, 2, geo, 64, 1.0) == body + head


def test_request_search_reads_every_masked_row():
    # fdm_a: two candidate forwards per step, each reads every masked
    # row of the canvas (64, 63, ..., 1)
    geo = {"gen_length": 64, "block_size": 32, "steps": 64,
           "cache_policy": "none"}
    one = flops.request_flops(fam, LLADA, 2, geo, 64, 1.0)
    three = flops.request_flops(fam, LLADA, 2, geo, 64, 3.0)
    cand = 2 * (64 * 2 * fam.layer_flops(LLADA, 128, 128)
                + sum(fam.head_flops(LLADA, m) for m in range(1, 65)))
    assert three - one == cand


def test_request_dual_counts_refresh_and_window():
    geo = {"gen_length": 64, "block_size": 32, "steps": 32,
           "cache_policy": "dual"}
    # 2 blocks: a refresh of 192 rows each, 16 window steps of 32 rows,
    # 2 tokens a step: the head reads 32, 30, ..., 2 rows
    refresh = 2 * 3 * fam.layer_flops(CHATGLM, 192, 192)
    steps = 2 * 16 * 3 * fam.layer_flops(CHATGLM, 32, 192)
    head = 2 * sum(fam.head_flops(CHATGLM, m) for m in range(2, 33, 2))
    assert flops.request_flops(fam, CHATGLM, 3, geo, 128, 1.0) == \
        refresh + steps + head


def test_confidence_bytes():
    assert flops.confidence_bytes(1024, 126464) == \
        1024 * 126464 * 4 + 1024 * 16


def test_cell_counts_as_pinned():
    """The counts the harness gave before the family's terms moved out of
    bench/flops.py: llada-8b at depth 17 under the offline cell's FDM-A
    geometry, and under a dual-cache geometry at prompt 512."""
    geo = {"gen_length": 128, "block_size": 32, "steps": 128,
           "cache_policy": "none"}
    assert flops.request_flops(fam, LLADA, 17, geo, 128, 3.0) == \
        755279931113472.0
    geo["cache_policy"] = "dual"
    assert flops.request_flops(fam, LLADA, 17, geo, 512, 1.0) == \
        52732266283008.0


def test_step_mfu_reads_the_cell_through_its_family():
    """``step_mfu`` of one synthetic batch of the offline cell: 4 rows,
    prompt 128, 3 forward equivalents a step, 19.1 s of decode spans."""
    from types import SimpleNamespace
    from bench import readers, run
    spec, cell, config, mix, limits = run.load_cell("llada8b-fdma-offline")
    spans = [{"name": "batch_assembly", "ph": "X", "ts": 0.0, "dur": 5.0,
              "args": {"batch_size": 4}}] + \
        [{"name": f"decode_block[{i}]", "ph": "X", "ts": 10.0 + i * 5e6,
          "dur": 4.775e6, "args": {}} for i in range(4)]
    rec = SimpleNamespace(ok=True, spans=spans, prompt=[0] * 128, stats={
        "forward_equivalents": 96.0, "steps": 128, "tokens_generated": 128})
    view = SimpleNamespace(records=[rec], config=config, mix=mix,
                           family=run.load_family(config["family"]),
                           peaks={"bf16_flops_per_s": 197e12})
    want = 100.0 * 4 * 755279931113472.0 / (4 * 4.775 * 197e12)
    assert abs(readers.step_mfu(view) - want) < 1e-12 * want
