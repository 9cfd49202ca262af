"""What the runners share: the program's configuration for a cell, the
seeded weights held against the program's layout."""
from __future__ import annotations

import jax

from chip_bench import weights

# keys of a configuration file that are the program's ModelConfig fields
_CFG_KEYS = ("num_layers", "d_model", "num_heads", "head_dim", "d_ff",
             "vocab_size", "num_kv_heads", "rope_theta", "norm_eps")
_SPIKING_KEYS = ("time_steps", "tau", "v_threshold", "soft_reset",
                 "attn_threshold_init")


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the registry
    entry in the file's dtype, refused if any size differs from the file
    (the file is the configuration as it is run)."""
    from repro.configs import get_config
    cfg = get_config(c["registry"], smoke=c.get("smoke", False))
    cfg = cfg.replace(dtype=c["dtype"])
    have = {k: getattr(cfg, k) for k in _CFG_KEYS if k in c}
    have.update({k: getattr(cfg.spiking, k) for k in _SPIKING_KEYS})
    if cfg.vision is not None:
        have.update(img_size=cfg.vision.img_size,
                    in_channels=cfg.vision.in_channels,
                    sps_stages=cfg.vision.sps_stages)
    if cfg.engine is not None and "packed_kv" in c:
        have["packed_kv"] = cfg.engine.packed_kv
    wrong = {k: (v, c[k]) for k, v in have.items() if c.get(k) != v}
    if wrong:
        raise ValueError(f"{c['registry']}: program and configuration file "
                         f"differ (program, file): {wrong}")
    return cfg


def seeded_params(c: dict, cfg, seed: int):
    from repro.models import registry
    params = weights.make_params(c, seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: registry.init(cfg, jax.random.PRNGKey(0))))
    return params
