"""Trinity-Mini (AfMoE, 26B total / ~3B active) — sparse experts with a
shared expert behind 3:1 sliding-window/full attention.

[hf:arcee-ai/Trinity-Mini config.json] 32 layers at width 2048; 32 query
heads over 4 KV heads of 128; a 2048-token window on three layers of
every four (full attention on layers 3, 7, ..., 31, which take no RoPE);
two leading dense layers (FFN 6144), then 128 routed experts of width
1024, top-8 by sigmoid score with a selection-only bias, normalized and
scaled by 2.826, plus one shared expert.  Per-head RMSNorm on q and k, a
sigmoid gate on the attention output, RMSNorm before and after both
sub-blocks, and embeddings scaled by sqrt(2048).
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="trinity-mini",
    family="moe",
    n_layers=32,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_head=128,
    d_ff=1024,
    vocab_size=200_192,
    n_experts=128,
    top_k=8,
    router="sigmoid",
    route_scale=2.826,
    expert_bias=True,
    n_shared_experts=1,
    n_dense_layers=2,
    dense_d_ff=6144,
    window=2048,
    global_every=4,
    rope_theta=10_000.0,
    rope_global=False,
    qk_norm=True,
    attn_gate=True,
    sandwich_norm=True,
    embed_scale=2048 ** 0.5,
    norm_eps=1e-5,
    source="hf:arcee-ai/Trinity-Mini",
))
