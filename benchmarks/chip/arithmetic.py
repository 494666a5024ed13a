"""Required operations and bytes, counted from shapes, and the table of
peaks. Nothing here asks the program what it did: a change that skips or
repeats work moves the measured time, never these counts.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` (``peaks.json``).
    A device the table does not hold is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def lm_matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    attention and MLP projections of every layer and the output head (the
    tied embedding counts once, as the head; the lookup is no matmul)."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["head_dim"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * d


def lm_train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward operations one trained token requires: 6 per
    matmul parameter, plus the causal attention scores and their weighted
    sum (2 matmuls of 2 flops per head dimension over (S + 1) / 2 keys on
    average, times 3 for forward and backward)."""
    attn = (6 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"] * (seq_len + 1))
    return 6.0 * lm_matmul_params(model) + attn


def lm_param_count(model: dict) -> int:
    """Every parameter of the decoder (tied embeddings, RMSNorm scales)."""
    d = model["hidden_size"]
    norms = (2 * model["num_hidden_layers"] + 1) * d
    return lm_matmul_params(model) + norms


def aggregation_bytes(n_params: int, m: int, chips: int,
                      dtype_bytes: int) -> float:
    """HBM bytes one robust aggregation of a gradient requires on each chip:
    the m worker values of the chip's 1/chips share of the coordinates read
    once, and the aggregate of that share written once."""
    share = n_params / chips
    return (m + 1) * share * dtype_bytes


def mlmc_aggregations(level: int, cap: int) -> int:
    """Distinct robust aggregations an MLMC round of ``level`` needs: levels
    0, J - 1 and J within the cap (at J = 1 level J - 1 is level 0, so
    two), level 0 alone beyond it."""
    if not 1 <= level <= cap:
        return 1
    return 2 if level == 1 else 3
