"""bench/flops.py against counts worked out by hand."""
from bench import flops

LLADA = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 32,
         "head_dim": 128, "d_ff": 12288, "vocab_size": 126464}
CHATGLM = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 2,
           "head_dim": 128, "d_ff": 13696, "vocab_size": 65024}


def test_llada_layer_by_hand():
    # 256 rows over 256 keys: q,k,v,o 4 * 2*256*4096*4096, attention
    # 2 * 2*256*256*4096, MLP 3 * 2*256*4096*12288
    want = 4 * 2 * 256 * 4096 * 4096 + 2 * 2 * 256 * 256 * 4096 \
        + 3 * 2 * 256 * 4096 * 12288
    assert flops.layer_flops(LLADA, 256, 256) == want == 112_742_891_520


def test_chatglm_layer_by_hand():
    # GQA: k and v project to 2 groups of 128
    want = 2 * 512 * 4096 * (32 + 4) * 128 + 2 * 512 * 4096 * 4096 \
        + 2 * 2 * 512 * 512 * 4096 + 3 * 2 * 512 * 4096 * 13696
    assert flops.layer_flops(CHATGLM, 512, 512) == want


def test_head_counts_only_read_rows():
    assert flops.head_flops(LLADA, 32) == 2 * 32 * 4096 * 126464
    assert flops.forward_flops(LLADA, 2, 256, 256, 32) == \
        2 * flops.layer_flops(LLADA, 256, 256) + flops.head_flops(LLADA, 32)


def test_request_none_one_token_per_step():
    # gen 64, block 32, 64 steps: each block's steps read 32, 31, ..., 1
    # masked rows of the block with the head
    geo = {"gen_length": 64, "block_size": 32, "steps": 64,
           "cache_policy": "none"}
    body = 64 * 2 * flops.layer_flops(LLADA, 128, 128)
    head = 2 * sum(flops.head_flops(LLADA, m) for m in range(1, 33))
    assert flops.request_flops(LLADA, 2, geo, 64, 1.0) == body + head


def test_request_search_reads_every_masked_row():
    # fdm_a: two candidate forwards per step, each reads every masked
    # row of the canvas (64, 63, ..., 1)
    geo = {"gen_length": 64, "block_size": 32, "steps": 64,
           "cache_policy": "none"}
    one = flops.request_flops(LLADA, 2, geo, 64, 1.0)
    three = flops.request_flops(LLADA, 2, geo, 64, 3.0)
    cand = 2 * (64 * 2 * flops.layer_flops(LLADA, 128, 128)
                + sum(flops.head_flops(LLADA, m) for m in range(1, 65)))
    assert three - one == cand


def test_request_dual_counts_refresh_and_window():
    geo = {"gen_length": 64, "block_size": 32, "steps": 32,
           "cache_policy": "dual"}
    # 2 blocks: a refresh of 192 rows each, 16 window steps of 32 rows,
    # 2 tokens a step: the head reads 32, 30, ..., 2 rows
    refresh = 2 * 3 * flops.layer_flops(CHATGLM, 192, 192)
    steps = 2 * 16 * 3 * flops.layer_flops(CHATGLM, 32, 192)
    head = 2 * sum(flops.head_flops(CHATGLM, m) for m in range(2, 33, 2))
    assert flops.request_flops(CHATGLM, 3, geo, 128, 1.0) == \
        refresh + steps + head


def test_confidence_bytes():
    assert flops.confidence_bytes(1024, 126464) == \
        1024 * 126464 * 4 + 1024 * 16
