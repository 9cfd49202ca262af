"""Operations and bytes of an analog LM with an expert layer, as its
configuration file (Hugging Face keys) states it, from shapes alone.

Counts of the chip's share: the router over every expert, and of the
experts only those held here, each hit by a token with the probability
that one of its ``num_experts_per_tok`` picks falls on it (uniform
routing: top_k / published experts a pick). A multiply-add is 2
operations. Norms, RoPE, softmax and SiLU are elementwise and left out.
"""
from __future__ import annotations

from chip_bench.flops import matmul


def _held_pairs_per_row(c: dict) -> float:
    """Expected (row, held expert) pairs a row makes."""
    return (c["num_experts_per_tok"] * c["num_experts"]
            / c["deployment"]["published_num_experts"])


def expert_pair(c: dict) -> int:
    """One row through one expert: gate, up and down."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return 2 * matmul(1, d, f) + matmul(1, f, d)


def token(c: dict) -> float:
    """Forward operations of one position apart from attending: every
    layer's Q/K/V/O projections, router and expected held-expert pairs,
    and the LM head."""
    d = c["hidden_size"]
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    layer = (matmul(1, d, qd) + 2 * matmul(1, d, kvd) + matmul(1, qd, d)
             + matmul(1, d, c["deployment"]["published_num_experts"])
             + _held_pairs_per_row(c) * expert_pair(c))
    return c["num_hidden_layers"] * layer + matmul(1, d, c["vocab_size"])


def attend(c: dict, context: int) -> int:
    """QK^T and AV of one position over ``context`` positions, every head,
    in one layer."""
    return 2 * matmul(1, context, c["num_attention_heads"] * c["head_dim"])


def layers_of(c: dict, kind: str) -> int:
    return sum(k == kind for k in c["layer_types"])


def gmm_ops(c: dict, rows: int) -> float:
    """The grouped matmuls' operations for ``rows`` real rows through
    every layer."""
    return c["num_hidden_layers"] * rows * _held_pairs_per_row(c) \
        * expert_pair(c)


def gmm_bytes(c: dict, rows: int, waves: int) -> float:
    """HBM bytes of the grouped matmuls: each wave reads every held
    expert's three matrices once a layer; each pair's rows go in and come
    out of each of the three matmuls, in the served dtype."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    w = 2 if c["dtype"] == "bfloat16" else 4
    weights = waves * c["num_experts"] * 3 * d * f * w
    rows_io = rows * _held_pairs_per_row(c) * 3 * (d + f) * w
    return c["num_hidden_layers"] * (weights + rows_io)
