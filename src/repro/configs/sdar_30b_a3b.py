"""sdar-30b-a3b — SDAR-30B-A3B-Chat, a block-diffusion MoE LM
[hf:JetLM/SDAR-30B-A3B-Chat, config.json].

48 layers, all MoE (128 routed experts, top-8, renormalised, expert
width 768, no shared expert), d_model=2048, GQA 32 query heads / 4 KV
heads of 128 with per-head q/k RMSNorm (its Qwen3-MoE base), rotary base
1e6, vocab 151,936, untied head.  Attention is block-causal: a block
sees itself and the blocks before it.  The config gives neither the
block length nor the mask token; 4 (the chat models' released decoding
block) and 151669 (an id of the tokenizer's added-token range above the
base vocabulary, not checked against the released tokenizer) are
assumed.
"""
import dataclasses

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="sdar-30b-a3b",
    arch_type="moe",
    source="hf:JetLM/SDAR-30B-A3B-Chat",
    num_layers=48,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=6144,                      # the config's dense width; every layer is MoE
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1_000_000.0,
    attn_block=4,
    mask_token_id=151669,
    moe=MoEConfig(num_experts=128, num_experts_per_tok=8, moe_d_ff=768),
    max_seq_len=32768,
)

# the CPU tests' preset: the same kinds at a small size — 16 experts
# (enough for eight shares of two), top-4, GQA, q/k norm, and a block
# length equal to the tiny cells' decode block
TINY = dataclasses.replace(
    CONFIG, name="sdar-30b-a3b-tiny", num_layers=2, d_model=128,
    num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512,
    attn_block=8, mask_token_id=511, max_seq_len=128, dtype="float32",
    moe=dataclasses.replace(CONFIG.moe, num_experts=16,
                            num_experts_per_tok=4, moe_d_ff=64))
