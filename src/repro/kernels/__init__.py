"""Pallas TPU kernels for FireFly-T's two compute hot-spots:

  spike_attention    — fused binary attention (binary engine, MXU form)
  spike_matmul       — block-sparse spike x weight matmul (sparse engine,
                       tile datapath: whole-tile occupancy skip)
  spike_decode       — gather-compacted spike matmul (sparse engine,
                       decoded datapath: cumsum prefix-compaction +
                       pow2 occupancy-bucket load balancing)
  lif                — LIF neurons in one pass over T_s (neuronal dynamics
                       module)
  popcount_attention — bit-packed AND-PopCount scores (faithful FPGA port,
                       kept for comparison; the MXU form wins on TPU)

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling; ``ops.py``
jit'd wrappers; ``ref.py`` pure-jnp oracles (tests sweep shapes/dtypes).
"""
from . import ops, ref
