"""mellum2-12b-a2.5b [dense + moe]: 28L d_model=2304 32H (GQA kv=4)
head_dim=128 vocab=98304; sliding-window attention (1024) on three layers
of every four, full attention on the fourth; every layer's FFN is 64
SwiGLU experts of width 896, top-8, softmax router with renormalised
top-k, no shared expert [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

RoPE theta 500000 on both kinds of layer; the full layers take YaRN
(factor 16 over an original 8192 positions). The model runs as the dense
family with ``moe`` set, so it takes the slotted decode path that
``launch/serve.BatchedServer`` needs.

CONFIG is one chip's share of an eight-chip expert-parallel deployment:
it holds 8 of each layer's 64 experts (the router keeps all 64 outputs);
attention and the head are whole on every chip.
"""
from .base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="dense",
    num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4, head_dim=128,
    d_ff=7168, vocab_size=98304,
    attn_type="local_global", global_every=4, window=1024,
    rope_theta=500000.0,
    yarn=YarnConfig(factor=16.0, original_max_position=8192,
                    beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.2772588722239782),
    act="silu", gated=True, norm_eps=1e-6, tie_embeddings=False,
    moe=MoEConfig(num_experts=8, router_width=64, top_k=8,
                  d_ff_expert=896, capacity_factor=0.0),
    remat=False,
)

SMOKE = CONFIG.replace(
    num_layers=8, window=8, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=128, vocab_size=256, dtype="float32",
    moe=MoEConfig(num_experts=4, router_width=16, top_k=8, d_ff_expert=32,
                  capacity_factor=0.0))
