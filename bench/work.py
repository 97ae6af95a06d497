"""The shared rules by which the work counts of ``bench/arch/<name>.py``
are made, and the chip's peaks.

A ternary projection is counted at 1.6 bits per weight (five trits to a
byte), whatever kernel or packing runs it; activations come in at their
served width (2 bytes, or 1 under int8 activations) and outputs leave in
bf16.  Model FLOPs count 2 per parameter touched per token; the LM head is
``2 * hidden_size * vocab_size`` of them.
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


def projection_work(m: int, k: int, n: int, act_bytes: int
                    ) -> tuple[float, float]:
    """``(flops, bytes)`` of one ``[m, k] x [k, n]`` ternary projection."""
    flops = 2.0 * m * k * n
    nbytes = k * n * PACKED_BYTES_PER_WEIGHT + m * k * act_bytes \
        + m * n * OUT_BYTES
    return flops, nbytes


def head_flops(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]
