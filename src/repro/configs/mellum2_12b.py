"""mellum2-12b [moe] — 28L d2304 32H (GQA kv=4, head 128), every layer
sparse: 64 experts of width 896, top-8 renormalized, no shared expert;
3:1 sliding(1024):full attention, RoPE base 500000 with YaRN (factor 16
over 8192 positions) on the full layers; vocab 98304, untied readout,
128k context.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab=98304,
    ffn="moe",
    n_experts=64,
    top_k=8,
    local_global_ratio=(3, 1),
    window=1024,
    rope_base=500000.0,
    global_yarn=Yarn(factor=16.0, original_max=8192, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.2772588722239782),
    tie_embeddings=False,
)

SMOKE = CONFIG.replace(
    name="mellum2-12b-smoke",
    n_layers=4,  # one sliding, sliding, sliding, full period
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=32,
    vocab=256,
    n_experts=16,
    top_k=4,
    window=8,
    # at head_dim 16 the ramp spans dims 1..4 of 8
    global_yarn=Yarn(factor=4.0, original_max=4096, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.1),
    attn_q_chunk=16,
    attn_kv_chunk=16,
    loss_chunk=16,
)
