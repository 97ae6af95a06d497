"""The one traffic generator: reads a mix's data file and draws requests.

A mix file (``bench/traffic/<name>.json``) holds only parameters:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does) or
  ``"closed"`` (the queue is kept ``queue_depth`` requests deep).
- ``arrivals`` (open loop): ``{"kind": "poisson", "rate_per_s": r}``.
- ``prompt_tokens`` / ``output_tokens``: a bounded length distribution,
  ``{"kind": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"kind": "uniform", "min", "max"}``.
- ``slots``, ``max_len``: the serving geometry the lengths need.
- ``warmup_s``: traffic run before the window opens.

Every seed gets the same work; the seed sets the prompts' tokens (and the
harness draws the weights from it).  Sizes are the distribution's quantiles
at evenly spaced probabilities:

- open loop: a segment of ``D`` seconds at rate ``r`` holds ``round(r * D)``
  arrivals; their gaps are the exponential quantiles, scaled to sum to
  ``D``, and they and the lengths are shuffled in one fixed order that the
  seed does not change.  A window holds about ten chat requests that live
  for seconds each, so an order drawn from the seed moved which tokens fell
  inside it: 16% between seeds against under 1% between two runs of one
  seed (output tokens per second on a TPU v5e);
- closed loop: a cycle of ``cycle`` requests at the low-discrepancy
  probabilities ``frac(k / phi)`` (prompts) and ``frac(k * sqrt 2)``
  (outputs), so any run of consecutive requests has lengths near the mean;
  the seed rotates the cycle.
"""

from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from pathlib import Path

import numpy as np

_NORMAL = statistics.NormalDist()
_PHI_INV = (math.sqrt(5.0) - 1.0) / 2.0
_SQRT2 = math.sqrt(2.0)
#: the stream that shuffles every open-loop schedule, whatever ``--seed`` is
ORDER_SEED = 0


def load(path: str | Path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


def quantile(dist: dict, u: float) -> int:
    """The length at probability ``u`` in (0, 1) of a bounded distribution."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["kind"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(v), lo), hi))
    if dist["kind"] == "uniform":
        return int(min(lo + math.floor(u * (hi - lo + 1)), hi))
    raise ValueError(f"unknown length distribution {dist['kind']!r}")


def rng(seed: int, stream: int) -> np.random.Generator:
    """One seeded stream; any whole-number seed, negative ones included."""
    return np.random.default_rng([seed % (1 << 64), stream])


@dataclass(frozen=True)
class Arrival:
    t: float            # seconds after the traffic starts
    prompt_len: int
    output_len: int


def open_segment(mix: dict, stream: int, start: float,
                 duration: float) -> list[Arrival]:
    """The arrivals of ``[start, start + duration)``: ``round(rate *
    duration)`` of them, the first at ``start``, in the fixed order."""
    arr = mix["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['kind']!r}")
    n = max(1, round(arr["rate_per_s"] * duration))
    g = rng(ORDER_SEED, stream)
    us = [(i + 0.5) / n for i in range(n)]
    gaps = np.array([-math.log(1.0 - u) for u in us])
    gaps = g.permutation(gaps * (duration / gaps.sum()))
    prompts = g.permutation([quantile(mix["prompt_tokens"], u) for u in us])
    outs = g.permutation([quantile(mix["output_tokens"], u) for u in us])
    t = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(float(t[i]), int(prompts[i]), int(outs[i]))
            for i in range(n)]


def open_schedule(mix: dict, warmup_s: float,
                  window_s: float) -> tuple[list[Arrival], list[Arrival]]:
    """Warm-up arrivals and window arrivals, drawn as separate segments so
    that the window holds the same requests whatever the warm-up's length."""
    return (open_segment(mix, 1, 0.0, warmup_s) if warmup_s > 0 else [],
            open_segment(mix, 2, warmup_s, window_s))


def closed_cycle(mix: dict, seed: int) -> list[tuple[int, int]]:
    """The ``(prompt_len, output_len)`` cycle a closed loop walks through."""
    n = int(mix["cycle"])
    r = int(rng(seed, 3).integers(n))
    out = []
    for j in range(n):
        k = (j + r) % n
        up = (0.5 / n + k * _PHI_INV) % 1.0
        uo = (0.5 / n + k * _SQRT2) % 1.0
        out.append((quantile(mix["prompt_tokens"], up),
                    quantile(mix["output_tokens"], uo)))
    return out


class Tokens:
    """Prompt contents: token ids drawn from the seed, request by request,
    in ``[2, vocab)``."""

    def __init__(self, seed: int, vocab: int):
        self._g = rng(seed, 4)
        self.vocab = vocab

    def draw(self, n: int) -> list[int]:
        return self._g.integers(2, self.vocab, size=n).tolist()
