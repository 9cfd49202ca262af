"""Config dataclasses for the model zoo and run shapes.

Every assigned architecture gets a ``configs/<id>.py`` exporting ``CONFIG``
(the exact published shape) and ``SMOKE`` (a reduced same-family config for
CPU tests). Input-shape sets live in ``configs/shapes.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.engine import EngineConfig
from repro.core.spiking import SpikingConfig


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int                # experts held in the parameters
    top_k: int
    d_ff_expert: int
    num_shared: int = 0
    first_k_dense: int = 0          # leading dense layers (deepseek/kimi style)
    first_dense_ff: int = 0         # d_ff of those dense layers
    # training forward only: 0 routes every token (dropless); serving
    # paths are always dropless
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    normalize_topk: bool = True     # renormalize top-k routing weights
    # the router's outputs, the model's whole expert count, where a chip
    # holds only ``num_experts`` of them (0: the router routes over the
    # experts held)
    router_width: int = 0

    @property
    def width(self) -> int:
        return self.router_width or self.num_experts


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rescaled RoPE (arXiv:2309.00071), as HF transformers'
    ``_compute_yarn_parameters`` reads a ``rope_type: yarn`` entry."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                # 0 -> ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    lora_mix: int = 32              # rank of data-dependent token-shift LoRA
    lora_decay: int = 64            # rank of data-dependent decay LoRA
    wkv_chunk: int = 0              # 0 = per-token scan; >0 = chunk-parallel
                                    # WKV (§Perf R1; exact, see models/rwkv)


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    kind: str                        # 'audio' | 'vision'
    num_embeds: int                  # frames / patches the stub provides
    embed_dim: int                   # pre-projector embedding dim
    projector_layers: int = 2        # mm projector MLP depth (vision)


@dataclasses.dataclass(frozen=True)
class VisionSpec:
    """Spikingformer / CIFAR-Net image input."""
    img_size: int = 32
    in_channels: int = 3
    sps_stages: int = 2              # maxpool stages in SPS (32->8 for CIFAR)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|rwkv|hybrid|encdec|vlm|spikingformer|cifarnet
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # attention
    attn_type: str = "full"          # full | swa | local_global
    window: int = 4096
    global_every: int = 6            # local_global: one global layer per N
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # rope of the full-attention layers, where it is not the plain rope
    # the window layers keep
    yarn: Optional[YarnConfig] = None
    # mlp
    act: str = "silu"                # silu | gelu | relu2
    gated: bool = True
    # misc
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    max_position_embeddings: int = 0  # >0 -> learned positions (whisper dec)
    # submodule configs
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    frontend: Optional[FrontendConfig] = None
    vision: Optional[VisionSpec] = None
    encoder_layers: int = 0          # whisper encoder depth
    encoder_seq: int = 1500          # whisper frame count (stubbed frontend)
    spiking: Optional[SpikingConfig] = None
    # dual-engine dispatch: step builders install this engine around the
    # forward pass, routing spike matmuls dense vs block-sparse AND
    # spiking attention jnp vs MXU-kernel vs popcount (core/engine.py).
    # The engine's packed_kv flag also selects the bit-packed spike KV
    # cache layout for spiking decode. None = always dense / jnp.
    engine: Optional[EngineConfig] = None
    dtype: str = "bfloat16"
    remat: bool = True

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class RunShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str                        # 'train' | 'prefill' | 'decode'

    @property
    def is_decode(self) -> bool:
        return self.mode == "decode"
