"""spikingformer-lm — a token-domain Spikingformer: the transformer family
in spiking mode (LIF activations over T_s steps, binary causal SSA).

This is the serve-path workload of the dual-engine overlay: prefill runs
the binary engine over the full prompt (engine-dispatched SSA), decode
runs token-by-token against a *bit-packed* spike KV cache (uint32 words,
the paper's 32x spike-RAM compression — `models/transformer.init_cache`
with `engine.packed_kv`), scoring with AND-PopCount. The shape mirrors
spikingformer-4-256 lifted to an LM (same blocks/width, GPT-2-ish vocab).
"""
from repro.core.engine import EngineConfig
from repro.core.spiking import SpikingConfig
from .base import ModelConfig

CONFIG = ModelConfig(
    name="spikingformer-lm", family="dense",
    num_layers=4, d_model=256, num_heads=8, num_kv_heads=8, head_dim=32,
    d_ff=1024, vocab_size=32000,
    attn_type="full", act="relu2", gated=False,
    spiking=SpikingConfig(time_steps=4),
    # binary='auto': full-size shapes clear the flop floor and run the
    # fused MXU kernel; packed_kv turns on the popcount decode cache.
    engine=EngineConfig(mode="auto"),
)

# head_dim=16 deliberately non-word-sized: the packed KV cache pads the
# final uint32 word with zero bits (AND-PopCount neutral), pinning the
# non-divisible packing path in every smoke run.
SMOKE = CONFIG.replace(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=128, vocab_size=64,
    spiking=SpikingConfig(time_steps=2), dtype="float32", remat=False)
