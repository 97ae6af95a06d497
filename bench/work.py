"""Operations and bytes the model's work needs, from its shapes alone.

The ternary projections are counted at 1.6 bits per weight (five trits to a
byte), whatever kernel or packing runs them; activations come in at their
served width (2 bytes, or 1 under int8 activations) and outputs leave in
bf16.  Model FLOPs count 2 per parameter touched per token, and attention
as ``QK^T`` plus ``PV`` over the live context.
"""

from __future__ import annotations

import json
from pathlib import Path

PACKED_BYTES_PER_WEIGHT = 1.6 / 8
OUT_BYTES = 2

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def projections(model: dict) -> list[tuple[str, int, int]]:
    """``(role, K, N)`` of each ternary projection of one layer."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    kv = model["num_key_value_heads"] * hd
    ff = model["intermediate_size"]
    return [("q", d, h * hd), ("k", d, kv), ("v", d, kv), ("o", h * hd, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def projection_work(m: int, k: int, n: int, act_bytes: int
                    ) -> tuple[float, float]:
    """``(flops, bytes)`` of one ``[m, k] x [k, n]`` ternary projection."""
    flops = 2.0 * m * k * n
    nbytes = k * n * PACKED_BYTES_PER_WEIGHT + m * k * act_bytes \
        + m * n * OUT_BYTES
    return flops, nbytes


def least_seconds(m: int, model: dict, act_bytes: int, peak_flops: float,
                  hbm: float) -> float:
    """Least time for every ternary projection of one forward over ``m``
    rows: per projection the larger of its compute and its memory bound."""
    per_layer = 0.0
    for _, k, n in projections(model):
        flops, nbytes = projection_work(m, k, n, act_bytes)
        per_layer += max(flops / peak_flops, nbytes / hbm)
    return per_layer * model["num_hidden_layers"]


def ternary_params(model: dict) -> int:
    return model["num_hidden_layers"] * sum(k * n for _, k, n in
                                            projections(model))


def attention_flops(model: dict, context: int) -> float:
    """``QK^T`` and ``PV`` of one token over ``context`` positions."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    return 4.0 * model["num_hidden_layers"] * h * hd * context


def head_flops(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def token_flops(model: dict, context: int, head: bool) -> float:
    """Model FLOPs of one token at ``context`` positions (itself included),
    with the LM head where that token's logits are needed."""
    return 2.0 * ternary_params(model) + attention_flops(model, context) + \
        (head_flops(model) if head else 0.0)
