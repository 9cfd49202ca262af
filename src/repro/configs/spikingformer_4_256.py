"""Spikingformer-4-256 — the paper's CIFAR-10 workload (§V-A):
4 encoder blocks, embedding dim 256, T_s=4, binary attention, pre-neuron
residuals. Trained with BrainCog in the paper; our spiking substrate
mirrors its LIF parameterization (core/spiking.py)."""
from repro.core.engine import EngineConfig
from repro.core.spiking import SpikingConfig
from .base import ModelConfig, VisionSpec

CONFIG = ModelConfig(
    name="spikingformer-4-256", family="spikingformer",
    num_layers=4, d_model=256, num_heads=8, num_kv_heads=8, head_dim=32,
    d_ff=1024, vocab_size=10,
    vision=VisionSpec(img_size=32, in_channels=3, sps_stages=2),
    spiking=SpikingConfig(time_steps=4),
    # dual-engine hot path: spike matmuls big enough to tile go through
    # the occupancy-skipping sparse kernel, and the SSA routes through
    # the binary engine (binary='auto' picks the fused MXU kernel once
    # the attention volume clears the same flop floor). The floor keeps
    # CPU smoke shapes on the plain XLA paths (engine dispatch is still
    # exercised — it just resolves dense/jnp there). sparse='auto' lets
    # eager (non-jit) sparse calls pick the decoded datapath from the
    # occupancy histogram when its sort leaves whole row groups dark
    # that the tile map cannot skip (DESIGN.md §9).
    engine=EngineConfig(mode="auto", sparse="auto", overlap="auto"),
)

SMOKE = CONFIG.replace(
    num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
    vision=VisionSpec(img_size=16, in_channels=3, sps_stages=2),
    spiking=SpikingConfig(time_steps=2), dtype="float32", remat=False)
