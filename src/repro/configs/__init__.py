"""Config registry: ``--arch <id>`` resolution.

ALL_ARCHS maps every architecture id (the assigned ones, the paper's own
models, and the benchmark's mellum2-12b-a2.5b) to its module exposing
CONFIG (published shape) and SMOKE (reduced config for CPU tests)."""
from . import (cifarnet, deepseek_moe_16b, gemma3_12b, granite_20b,
               h2o_danube3_4b, hymba_1_5b, kimi_k2_1t_a32b,
               llava_next_mistral_7b, mellum2_12b_a2_5b, nemotron_4_15b,
               rwkv6_3b, shapes, spikingformer_4_256, spikingformer_8_512,
               spikingformer_lm, whisper_small)
from .base import ModelConfig, RunShape
from .shapes import SHAPES

_MODULES = {
    "nemotron-4-15b": nemotron_4_15b,
    "gemma3-12b": gemma3_12b,
    "h2o-danube-3-4b": h2o_danube3_4b,
    "granite-20b": granite_20b,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "rwkv6-3b": rwkv6_3b,
    "hymba-1.5b": hymba_1_5b,
    "whisper-small": whisper_small,
    "llava-next-mistral-7b": llava_next_mistral_7b,
    "spikingformer-4-256": spikingformer_4_256,
    "spikingformer-8-512": spikingformer_8_512,
    "spikingformer-lm": spikingformer_lm,
    "cifarnet": cifarnet,
    "mellum2-12b-a2.5b": mellum2_12b_a2_5b,
}

# the 10 assigned cells
ASSIGNED_ARCHS = ("nemotron-4-15b", "gemma3-12b", "h2o-danube-3-4b",
                  "granite-20b", "kimi-k2-1t-a32b", "deepseek-moe-16b",
                  "rwkv6-3b", "hymba-1.5b", "whisper-small",
                  "llava-next-mistral-7b")
# the paper's own models
PAPER_ARCHS = ("spikingformer-4-256", "spikingformer-8-512",
               "spikingformer-lm", "cifarnet")
ALL_ARCHS = tuple(_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = _MODULES[name]
    return mod.SMOKE if smoke else mod.CONFIG


def get_shape(name: str) -> RunShape:
    return SHAPES[name]
