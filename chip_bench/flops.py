"""Operations and bytes of the work a cell asks for, from shapes alone.

Dense-equivalent counts of the published layer equations: a multiply-add
is 2 operations, and a {0,1} spike operand counts as dense. So the counts
do not move when a later change skips zeros, picks another kernel, or
recomputes: such a change shows as a higher share of the peak. Norms,
LIF updates and pools are elementwise and left out (a few percent).
"""
from __future__ import annotations


def matmul(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def spike_matmul_bytes(m: int, k: int, n: int, w_bytes: int,
                       out_bytes: int) -> int:
    """HBM bytes of one (m, k) spikes x (k, n) weights product: spikes at
    one byte, weights and outputs in their dtype, each moved once."""
    return m * k + k * n * w_bytes + m * n * out_bytes


def encoder_layer(c: dict, tokens: int, context: int) -> int:
    """One spiking encoder layer over ``tokens`` rows of one time step,
    each attending over ``context`` positions: Q/K/V/O projections, the
    two MLP projections, and QK^T and AV over every head."""
    d, ff = c["d_model"], c["d_ff"]
    qd = c["num_heads"] * c["head_dim"]
    kvd = c.get("num_kv_heads", c["num_heads"]) * c["head_dim"]
    return (matmul(tokens, d, qd) + 2 * matmul(tokens, d, kvd)
            + matmul(tokens, qd, d) + 2 * matmul(tokens, d, ff)
            + 2 * matmul(tokens, context, qd))


def sps_stem(c: dict) -> int:
    """The four 3x3 convolutions of the SPS stem, one image, one step."""
    size, stages = c["img_size"], c["sps_stages"]
    chans = [c["in_channels"]] + list(c["sps_channels"])
    total = 0
    for i in range(4):
        total += matmul(size * size, 9 * chans[i], chans[i + 1])
        if i >= 4 - stages:
            size //= 2
    return total


def vision_tokens(c: dict) -> int:
    return (c["img_size"] // 2 ** c["sps_stages"]) ** 2


def vision_image(c: dict) -> int:
    """Forward operations per image: stem and blocks at every time step,
    and the classification head once."""
    l = vision_tokens(c)
    per_step = sps_stem(c) + c["num_layers"] * encoder_layer(c, l, l)
    return c["time_steps"] * per_step + matmul(1, c["d_model"],
                                               c["vocab_size"])


def lm_token(c: dict, context: int) -> int:
    """Forward operations for one token that attends over ``context``
    positions (itself included): every layer at every time step, and the
    LM head once on the rate-decoded stream."""
    return (c["time_steps"] * c["num_layers"] * encoder_layer(c, 1, context)
            + matmul(1, c["d_model"], c["vocab_size"]))
