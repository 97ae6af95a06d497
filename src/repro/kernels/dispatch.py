"""Unified ternary-matmul dispatch: one entry point, many kernels.

The paper's central finding is that the best ternary-matmul strategy depends
on activation dtype and operand shape (LUT wins FP16 compute, the benefit is
minimal at INT8; packed streaming wins when decode is bandwidth-bound).  This
module makes that trade-off a *runtime* decision instead of a per-callsite
hard-wiring:

  * a **registry** of every ternary matmul implementation in this package
    (``ref``, ``lut_onehot``, ``lut_gather``, ``dequant_packed``,
    ``signflip``, ``w2a8``, the TL2 two-trit LUT family ``tl2``/``tl2_ref``,
    plus the grouped batched-expert family
    ``grouped_ref``/``grouped_dequant``/``grouped_w2a8``/``grouped_tl2``)
    with its supported activation dtypes and shape constraints,
  * a **static prior** derived from the analytical cost model
    (:mod:`repro.core.cost_model`): per-MAC gate cost of each datapath plus a
    weight-bytes-streamed term, so small-M (decode) shapes lean to the packed
    1.6 b/w paths and large-M (prefill) shapes to the cheapest compute,
  * a **benchmark-driven autotune cache** keyed on
    ``(M, K, N, activation_dtype, backend)`` — grouped problems prepend the
    expert count ``E`` — persisted to disk (``REPRO_AUTOTUNE_CACHE``, default
    ``~/.cache/repro/autotune.json``), populated by :func:`autotune` /
    ``benchmarks/autotune_sweep.py``,
  * two public entry points::

        y = ternary_matmul(x, w, policy="auto")          # cache → prior
        y = ternary_matmul(x, w, policy="fixed:signflip")  # reproducible pin
        y = grouped_ternary_matmul(t, gw)  # [E, C, K] × stacked experts

Shape convention: ``x [..., K]`` activations, weights ``[N, K]`` (out-major,
as everywhere in this repo), result ``[..., N]``.  Grouped (MoE expert)
problems carry a leading expert dim on both operands: ``x [E, ..., K]``
against a :class:`GroupedTernaryWeight` holding stacked ``[E, N, K]`` trits
(stored as ``[E, N, ceil(K/5)]`` packed bytes) with a per-expert rank-1
scale.  All kernels consume *unscaled* trits; the BitNet absmean scale is
applied once on the way out.

On CPU the Pallas kernels run in interpret mode, which is functionally exact
but orders of magnitude slower than XLA — the prior carries a backend-aware
penalty so ``auto`` never routes a CPU serving path through an interpreted
kernel unless the autotune cache has measured otherwise.  On TPU the kernels
that Mosaic cannot compile (``KernelSpec.tpu=False``: the LUT, sign-flip and
TL2 Pallas kernels) are never eligible, and under a partitioned serving mesh
no Pallas kernel is (see :func:`tpu_exclusion`).
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.core import cost_model as cm
from repro.kernels.dequant_matmul import packed_matmul
from repro.kernels.grouped_matmul import grouped_packed_matmul, grouped_w2a8_matmul
from repro.kernels.lut_matmul import lut_matmul
from repro.kernels.signflip_matmul import signflip_matmul
from repro.kernels.tl2_matmul import (TRITS_PER_WORD, pack_tl2,
                                      repack_base3_to_tl2, tl2_matmul,
                                      tl2_matmul_ref)
from repro.kernels.w2a8_matmul import w2a8_matmul

__all__ = [
    "TernaryWeight", "GroupedTernaryWeight", "KernelSpec", "REGISTRY",
    "register_kernel", "kernel_names", "get_kernel", "eligible_kernels",
    "select_kernel", "static_prior", "tpu_exclusion", "ternary_matmul",
    "grouped_ternary_matmul", "autotune",
    "AutotuneCache", "get_autotune_cache", "reset_autotune_cache",
    "ShardInfo", "shard_scope", "current_shard_info",
    "DEFAULT_POLICY_ENV",
]

DEFAULT_POLICY_ENV = "REPRO_TERNARY_POLICY"
CACHE_PATH_ENV = "REPRO_AUTOTUNE_CACHE"

#: roofline-ish exchange rate between the two prior terms: how many
#: gate-cycles of compute one byte of HBM weight traffic is "worth".
GATES_PER_BYTE = 2048.0

#: multiplier applied to Pallas kernels when the backend executes them in
#: interpret mode (CPU) — functional, but never competitive.
INTERPRET_PENALTY = 1e4


# ---------------------------------------------------------------------------
# Unified weight container
# ---------------------------------------------------------------------------


def _concrete(v: jax.Array) -> bool:
    """Derived encodings are cached only when concrete: a value computed
    while tracing (e.g. the weight arrived as a jit argument) is a Tracer
    and caching it would leak it into later traces
    (UnexpectedTracerError)."""
    return not isinstance(v, jax.core.Tracer)


class TernaryWeight:
    """A ternary weight matrix with lazily derived per-kernel encodings.

    Holds the logical ``[N, K]`` trit matrix (out-major) and its BitNet
    absmean ``scale``; the base-3 packed bytes (dequant/w2a8 paths) and the
    mu-group LUT keys are derived on first use and cached, so a weight
    prepared once can be routed through any registered kernel.
    """

    def __init__(self, w_t: jax.Array | None = None, scale=1.0, *,
                 packed: jax.Array | None = None, k: int | None = None,
                 mu: int = 3):
        if w_t is None and packed is None:
            raise ValueError("need trits or packed bytes")
        if w_t is not None and w_t.dtype != jnp.int8:
            w_t = w_t.astype(jnp.int8)
        self._w_t = w_t
        self._packed = packed
        self._k = int(w_t.shape[-1]) if w_t is not None else int(k)
        self.scale = scale
        self.mu = mu
        self._keys: dict[int, jax.Array] = {}
        self._tl2: jax.Array | None = None

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_dense(cls, w: jax.Array, *, mu: int = 3) -> "TernaryWeight":
        """Master fp weights ``[N, K]`` → ternarized container."""
        from repro.core.quantization import ternarize

        w_t, scale = ternarize(w)
        return cls(w_t, scale, mu=mu)

    @classmethod
    def from_ternary(cls, w_t: jax.Array, scale=1.0, *, mu: int = 3) -> "TernaryWeight":
        return cls(w_t, scale, mu=mu)

    @classmethod
    def from_packed(cls, packed: jax.Array, scale, k: int, *,
                    mu: int = 3) -> "TernaryWeight":
        """Deployment artifact ``{"packed" [N, ceil(K/5)], "scale"}`` → container."""
        return cls(None, scale, packed=packed, k=k, mu=mu)

    # -- shapes -------------------------------------------------------------

    @property
    def out_features(self) -> int:
        src = self._w_t if self._w_t is not None else self._packed
        return int(src.shape[0])

    @property
    def in_features(self) -> int:
        return self._k

    # -- encodings (cached via module-level _concrete gate) ------------------

    def trits(self) -> jax.Array:
        """Dense ``[N, K]`` int8 trits (ref/signflip paths)."""
        if self._w_t is not None:
            return self._w_t
        w_t = encoding.unpack_base3(self._packed, self._k)
        if _concrete(w_t):
            self._w_t = w_t
        return w_t

    def packed(self) -> jax.Array:
        """Base-3 packed bytes ``[N, ceil(K/5)]`` (dequant/w2a8 paths)."""
        if self._packed is not None:
            return self._packed
        packed = encoding.pack_base3(self._w_t)
        if _concrete(packed):
            self._packed = packed
        return packed

    def keys(self, mu: int | None = None) -> jax.Array:
        """Group keys ``[N, ceil(K/mu)]`` (LUT paths)."""
        mu = mu or self.mu
        if mu in self._keys:
            return self._keys[mu]
        keys = encoding.encode_weight_matrix(self.trits(), mu)
        if _concrete(keys):
            self._keys[mu] = keys
        return keys

    def tl2(self) -> jax.Array:
        """TL2 base-9 words ``[N, ceil(K/10)]`` uint16 (tl2 paths)."""
        if self._tl2 is not None:
            return self._tl2
        if self._packed is not None:
            words = repack_base3_to_tl2(self._packed, self._k)
        else:
            words = pack_tl2(self._w_t)
        if _concrete(words):
            self._tl2 = words
        return words


def _as_weight(w, scale, mu) -> TernaryWeight:
    if isinstance(w, TernaryWeight):
        return w
    if isinstance(w, encoding.PackedTernary):
        return TernaryWeight.from_packed(w.data, w.scale, w.shape[1],
                                         mu=mu or 3)
    w = jnp.asarray(w)
    if w.dtype != jnp.int8:
        raise TypeError(
            "ternary_matmul weights must be a TernaryWeight, PackedTernary, "
            f"or int8 trit matrix; got dtype {w.dtype}. Use "
            "TernaryWeight.from_dense(w) to ternarize master weights.")
    return TernaryWeight.from_ternary(w, 1.0 if scale is None else scale,
                                      mu=mu or 3)


class GroupedTernaryWeight:
    """A stacked per-expert ternary weight ``[E, N, K]`` with per-expert
    scales ``[E]`` — the MoE analogue of :class:`TernaryWeight`.

    The serving artifact form is ``{"packed": uint8 [E, N, ceil(K/5)+pad],
    "scale": [E]}`` (``quantize_for_serving`` pads the byte dim for TP
    shardability; kernels slice decode at the logical ``K``).  Dense trits
    and packed bytes are derived lazily from each other, with the same
    concreteness-gated caching as the dense container.
    """

    def __init__(self, w_t: jax.Array | None = None, scale=1.0, *,
                 packed: jax.Array | None = None, k: int | None = None,
                 mu: int = 3):
        if w_t is None and packed is None:
            raise ValueError("need trits or packed bytes")
        if w_t is not None and w_t.dtype != jnp.int8:
            w_t = w_t.astype(jnp.int8)
        if (w_t if w_t is not None else packed).ndim != 3:
            raise ValueError(
                "grouped weights are stacked [E, N, K] trits / "
                f"[E, N, ceil(K/5)] bytes; got ndim "
                f"{(w_t if w_t is not None else packed).ndim}")
        self._w_t = w_t
        self._packed = packed
        self._k = int(w_t.shape[-1]) if w_t is not None else int(k)
        self.scale = scale
        self.mu = mu
        self._tl2: jax.Array | None = None

    @classmethod
    def from_ternary(cls, w_t: jax.Array, scale=1.0, *,
                     mu: int = 3) -> "GroupedTernaryWeight":
        return cls(w_t, scale, mu=mu)

    @classmethod
    def from_packed(cls, packed: jax.Array, scale, k: int, *,
                    mu: int = 3) -> "GroupedTernaryWeight":
        """Stacked deployment artifact ``{"packed" [E, N, ceil(K/5)],
        "scale" [E]}`` → container (this is what ``layers._expert_matmul``
        receives after the per-layer scan slice)."""
        return cls(None, scale, packed=packed, k=k, mu=mu)

    # -- shapes -------------------------------------------------------------

    @property
    def n_experts(self) -> int:
        src = self._w_t if self._w_t is not None else self._packed
        return int(src.shape[0])

    @property
    def out_features(self) -> int:
        src = self._w_t if self._w_t is not None else self._packed
        return int(src.shape[1])

    @property
    def in_features(self) -> int:
        return self._k

    # -- encodings (cached via module-level _concrete gate) ------------------

    def trits(self) -> jax.Array:
        """Dense stacked ``[E, N, K]`` int8 trits.  NOTE: this materializes
        the full expert stack — kernels should prefer :meth:`packed` and
        decode tile-by-tile (or per expert)."""
        if self._w_t is not None:
            return self._w_t
        w_t = encoding.unpack_base3(self._packed, self._k)
        if _concrete(w_t):
            self._w_t = w_t
        return w_t

    def packed(self) -> jax.Array:
        """Stacked base-3 packed bytes ``[E, N, ceil(K/5)]``."""
        if self._packed is not None:
            return self._packed
        packed = encoding.pack_base3(self._w_t)
        if _concrete(packed):
            self._packed = packed
        return packed

    def tl2(self) -> jax.Array:
        """Stacked TL2 base-9 words ``[E, N, ceil(K/10)]`` uint16."""
        if self._tl2 is not None:
            return self._tl2
        if self._packed is not None:
            words = repack_base3_to_tl2(self._packed, self._k)
        else:
            words = pack_tl2(self._w_t)
        if _concrete(words):
            self._tl2 = words
        return words


def _as_grouped_weight(w, scale, mu) -> GroupedTernaryWeight:
    if isinstance(w, GroupedTernaryWeight):
        return w
    w = jnp.asarray(w)
    if w.dtype != jnp.int8 or w.ndim != 3:
        raise TypeError(
            "grouped_ternary_matmul weights must be a GroupedTernaryWeight "
            f"or a stacked int8 trit array [E, N, K]; got dtype {w.dtype} "
            f"ndim {w.ndim}")
    return GroupedTernaryWeight.from_ternary(
        w, 1.0 if scale is None else scale, mu=mu or 3)


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelSpec:
    """One registered ternary-matmul implementation.

    Dense kernels: ``run(x2, w, mu, interpret)`` consumes ``x2 [M, K]`` and
    returns the *unscaled* ``[M, N] float32`` product against ``w.trits()``.

    Grouped kernels (``grouped=True``): ``run(x3, gw, mu, interpret)``
    consumes ``x3 [E, C, K]`` against a :class:`GroupedTernaryWeight` and
    returns unscaled ``[E, C, N]`` (float32, or int32 cast to f32).  A
    grouped problem is keyed by its expert count ``e``; dense and grouped
    kernels are never eligible for each other's problems.
    """

    name: str
    run: Callable
    act_dtypes: frozenset
    pallas: bool                      # interpret-mode on CPU → prior penalty
    prior_per_mac: Callable           # (K, N, coeffs, mu) -> gates per MAC
    weight_bytes: Callable            # (K, N, mu) -> HBM bytes streamed
    describe: str = ""
    constraint: Callable | None = None  # (M, K, N, act_dtype) -> bool
    grouped: bool = False             # batched-expert (MoE) kernel
    grouped_variant: str | None = None  # dense kernel's grouped analogue
                                        # (fixed:<dense> pins map through it)
    tpu: bool = True                  # compiles for TPU (Mosaic/XLA); False
                                      # kernels are never eligible there

    def supports(self, m: int, k: int, n: int, act_dtype: str,
                 e: int | None = None) -> bool:
        if (e is not None) != self.grouped:
            return False
        if act_dtype not in self.act_dtypes:
            return False
        if self.constraint is not None and not self.constraint(m, k, n, act_dtype):
            return False
        return True


REGISTRY: dict[str, KernelSpec] = {}

_FLOAT_DTYPES = frozenset({"float32", "bfloat16", "float16"})
_ALL_DTYPES = _FLOAT_DTYPES | {"int8"}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    if spec.name in REGISTRY:
        raise ValueError(f"kernel {spec.name!r} already registered")
    REGISTRY[spec.name] = spec
    return spec


def kernel_names() -> list[str]:
    return list(REGISTRY)


def get_kernel(name: str) -> KernelSpec:
    if name not in REGISTRY:
        raise KeyError(f"unknown kernel {name!r}; registered: {sorted(REGISTRY)}")
    return REGISTRY[name]


def eligible_kernels(m: int, k: int, n: int, act_dtype: str,
                     e: int | None = None,
                     backend: str | None = None) -> list[KernelSpec]:
    """Kernels that support the problem; with ``backend``, also only those
    that can run there (:func:`tpu_exclusion`)."""
    return [s for s in REGISTRY.values()
            if s.supports(m, k, n, act_dtype, e)
            and (backend is None or tpu_exclusion(s, backend) is None)]


# -- kernel adapters --------------------------------------------------------


def _to_f32(x2: jax.Array) -> jax.Array:
    return x2.astype(jnp.float32)


def _run_ref(x2, w, mu, interpret):
    # Pure-XLA oracle/deployment path: unpack (if packed) + dense f32 matmul.
    # This is both the correctness reference for every other kernel and the
    # fastest CPU execution of the packed serving artifact.
    wt = w.trits().astype(jnp.float32)
    return _to_f32(x2) @ wt.T


def _run_lut(fetch):
    def run(x2, w, mu, interpret):
        keys = w.keys(mu)
        G = keys.shape[-1]
        pad = G * mu - x2.shape[-1]
        if pad:
            x2 = jnp.pad(x2, ((0, 0), (0, pad)))
        return lut_matmul(_to_f32(x2), keys, mu, fetch=fetch, interpret=interpret)

    return run


def _run_dequant(x2, w, mu, interpret):
    # bf16 stays bf16 (trits are exact in bf16, accumulation is f32); other
    # dtypes, int8 included, run the float datapath in f32
    xk = x2 if x2.dtype == jnp.bfloat16 else _to_f32(x2)
    return packed_matmul(xk, w.packed(), w.in_features, interpret=interpret)


def _run_signflip(x2, w, mu, interpret):
    return signflip_matmul(_to_f32(x2), w.trits(), interpret=interpret)


def _run_w2a8(x2, w, mu, interpret):
    y = w2a8_matmul(x2, w.packed(), w.in_features, interpret=interpret)
    return y.astype(jnp.float32)


def _run_tl2(x2, w, mu, interpret):
    # tl2_matmul zero-pads x to the unpacked word width and casts to f32
    # itself (int8 casts losslessly), so int8 and float share one path.
    return tl2_matmul(x2, w.tl2(), w.in_features, interpret=interpret)


def _run_tl2_ref(x2, w, mu, interpret):
    return tl2_matmul_ref(x2, w.tl2(), w.in_features)


# -- grouped (batched-expert) adapters --------------------------------------


def _run_grouped_ref(x3, w, mu, interpret):
    # Pure-XLA grouped oracle/CPU-serving path: map over experts, decoding
    # each expert's packed bytes straight to f32 (one typed-table gather)
    # right before its matmul.  Only ONE expert's dense [N, K] tile is ever
    # live — the full-stack [E, N, K] dequant the eager einsum path paid
    # never materializes — and the jaxpr stays E-independent (a scan), so
    # llama4's E=128 stacks trace as fast as a 2-expert smoke config.
    k = w.in_features

    def one(args):
        xe, pe = args
        we = encoding.unpack_base3_to(pe, k, jnp.float32)  # [N, K] f32
        return _to_f32(xe) @ we.T

    return jax.lax.map(one, (x3, w.packed()))


def _run_grouped_dequant(x3, w, mu, interpret):
    return grouped_packed_matmul(_to_f32(x3), w.packed(), w.in_features,
                                 interpret=interpret)


def _run_grouped_w2a8(x3, w, mu, interpret):
    y = grouped_w2a8_matmul(x3, w.packed(), w.in_features,
                            interpret=interpret)
    return y.astype(jnp.float32)


def _run_grouped_tl2(x3, w, mu, interpret):
    # lax.map of the XLA pair-table ref over the expert stack: only one
    # expert's [C, N] tile plus its [N, G, 9] one-hot is live at a time and
    # the jaxpr stays E-independent (a scan), mirroring grouped_ref.
    k = w.in_features

    def one(args):
        xe, we = args
        return tl2_matmul_ref(xe, we, k)

    return jax.lax.map(one, (x3, w.tl2()))


# -- cost-model hooks (static prior) ----------------------------------------


def _per_mac_lut(k, n, c, mu):
    return cm.area_per_throughput(mu, max(k, mu), max(n, 1), c)


def _per_mac_dequant(k, n, c, mu):
    return cm.area_gates_dequant_baseline(k, n, c) / max(k * n, 1)


def _per_mac_signflip(k, n, c, mu):
    return cm.area_gates_signflip_baseline(k, n, c) / max(k * n, 1)


def _per_mac_dense(k, n, c, mu):
    # full-width multiplier + accumulator per MAC, no dequant cell
    return c.a_mul + c.a_add


def _bytes_dense(k, n, mu):
    return 2.0 * k * n          # bf16 dense weights


def _bytes_trits(k, n, mu):
    return float(k * n)         # int8 trit stream (signflip)


def _bytes_packed(k, n, mu):
    return n * math.ceil(k / encoding.TRITS_PER_BYTE)   # 1.6 b/w base-3


def _bytes_keys(k, n, mu):
    nbytes = 1 if encoding.key_bits(mu) <= 8 else 2
    return n * math.ceil(k / mu) * nbytes


def _per_mac_tl2(k, n, c, mu):
    # TL2 is the mu=2 point of the paper's LUT family: a trit *pair* keys a
    # 9-entry table, independent of the base-3 group size in play.
    return cm.area_per_throughput(2, max(k, 2), max(n, 1), c)


def _bytes_tl2(k, n, mu):
    return 2.0 * n * math.ceil(k / TRITS_PER_WORD)   # 1.6 b/w base-9 uint16


def _bytes_tl2_onehot_f32(k, n, mu):
    # the XLA TL2 refs materialize the decoded [N, ceil(K/2), 9] f32 one-hot
    # fetch operand through memory; charge that stream (as _bytes_decoded_f32
    # does for grouped_ref) so CPU serving keeps preferring the plain refs
    return 4.0 * 9.0 * n * math.ceil(k / 2)


register_kernel(KernelSpec(
    name="ref", run=_run_ref, act_dtypes=_ALL_DTYPES, pallas=False,
    prior_per_mac=_per_mac_dense, weight_bytes=_bytes_dense,
    grouped_variant="grouped_ref",
    describe="pure-XLA dense f32 matmul over decoded trits (oracle + CPU "
             "serving path)"))

register_kernel(KernelSpec(
    name="lut_onehot", run=_run_lut("onehot"), act_dtypes=_ALL_DTYPES,
    pallas=True, prior_per_mac=_per_mac_lut, weight_bytes=_bytes_keys,
    describe="two-phase LUT Pallas kernel, MXU-resident signed one-hot fetch",
    constraint=lambda m, k, n, d: True,
    tpu=False))  # Mosaic: "lhs contracting dims must be of size 1"

register_kernel(KernelSpec(
    name="lut_gather", run=_run_lut("gather"), act_dtypes=_ALL_DTYPES,
    pallas=True, prior_per_mac=_per_mac_lut, weight_bytes=_bytes_keys,
    describe="two-phase LUT Pallas kernel, VPU dynamic-gather fetch "
             "(literal read-out MUX)",
    constraint=lambda m, k, n, d: True,
    tpu=False))  # Mosaic: "Only 2D gather is supported"

register_kernel(KernelSpec(
    name="dequant_packed", run=_run_dequant, act_dtypes=_ALL_DTYPES,
    pallas=True, prior_per_mac=_per_mac_dequant, weight_bytes=_bytes_packed,
    grouped_variant="grouped_dequant",
    describe="base-3 packed streaming dequant Pallas kernel (1.6 b/w)"))

register_kernel(KernelSpec(
    name="signflip", run=_run_signflip, act_dtypes=_ALL_DTYPES,
    pallas=True, prior_per_mac=_per_mac_signflip, weight_bytes=_bytes_trits,
    describe="binary-plane MXU sign-flip baseline (Fig. 1 middle)",
    tpu=False))  # Mosaic: "Target does not support this comparison"

register_kernel(KernelSpec(
    name="w2a8", run=_run_w2a8, act_dtypes=frozenset({"int8"}),
    pallas=True, prior_per_mac=_per_mac_dequant, weight_bytes=_bytes_packed,
    grouped_variant="grouped_w2a8",
    describe="W1.58A8 exact int8×trit→int32 kernel (paper Table I operating "
             "point); requires pre-quantized int8 activations"))

register_kernel(KernelSpec(
    name="tl2", run=_run_tl2, act_dtypes=_ALL_DTYPES, pallas=True,
    prior_per_mac=_per_mac_tl2, weight_bytes=_bytes_tl2,
    grouped_variant="grouped_tl2",
    describe="TL2 two-trit → 9-entry LUT Pallas kernel (base-9 uint16 "
             "packing, 1.6 b/w; bitnet.cpp TL2 / T-MAC idiom)",
    tpu=False))  # Mosaic: [bo, 64]-word block not (8, 128)-aligned

register_kernel(KernelSpec(
    name="tl2_ref", run=_run_tl2_ref, act_dtypes=_ALL_DTYPES, pallas=False,
    prior_per_mac=_per_mac_tl2, weight_bytes=_bytes_tl2_onehot_f32,
    grouped_variant="grouped_tl2",
    describe="pure-XLA TL2 reference: dense pair-table build + one-hot "
             "fetch contractions over base-9 packed words"))


def _bytes_decoded_f32(k, n, mu):
    # grouped_ref streams the packed bytes AND round-trips a decoded f32
    # tile per expert through memory; charge the decoded stream so packed
    # in-VMEM decode wins the bandwidth-bound (decode) regime on hardware
    return 4.0 * k * n


register_kernel(KernelSpec(
    name="grouped_ref", run=_run_grouped_ref, act_dtypes=_ALL_DTYPES,
    pallas=False, grouped=True, prior_per_mac=_per_mac_dense,
    weight_bytes=_bytes_decoded_f32,
    describe="pure-XLA batched-expert matmul: lax.map over experts with "
             "per-expert f32 table decode (grouped oracle + CPU MoE serving "
             "path; no [E, N, K] dense intermediate)"))

register_kernel(KernelSpec(
    name="grouped_dequant", run=_run_grouped_dequant, act_dtypes=_ALL_DTYPES,
    pallas=True, grouped=True, prior_per_mac=_per_mac_dequant,
    weight_bytes=_bytes_packed,
    describe="grouped base-3 packed streaming dequant Pallas kernel: expert "
             "grid dim, tile-wise VMEM trit decode (1.6 b/w MoE path)"))

register_kernel(KernelSpec(
    name="grouped_w2a8", run=_run_grouped_w2a8,
    act_dtypes=frozenset({"int8"}), pallas=True, grouped=True,
    prior_per_mac=_per_mac_dequant, weight_bytes=_bytes_packed,
    describe="grouped W1.58A8 exact int8×trit→int32 Pallas kernel with an "
             "expert grid dim and per-expert rank-1 rescale on the way out"))

register_kernel(KernelSpec(
    name="grouped_tl2", run=_run_grouped_tl2, act_dtypes=_ALL_DTYPES,
    pallas=False, grouped=True, prior_per_mac=_per_mac_tl2,
    weight_bytes=_bytes_tl2_onehot_f32,
    describe="grouped TL2: lax.map of the XLA pair-table reference over the "
             "stacked base-9 expert words (no dense [E, N, K] intermediate)"))


# ---------------------------------------------------------------------------
# Static prior (analytical cost model)
# ---------------------------------------------------------------------------


def static_prior(spec: KernelSpec, m: int, k: int, n: int, act_dtype: str,
                 backend: str | None = None, mu: int = 3,
                 e: int | None = None) -> float:
    """Analytical cost score for running ``spec`` on an ``[m,k]×[n,k]``
    matmul: per-MAC gate cost from the paper's area model (Eqs. 5-10 /
    Fig. 1 baselines) × MAC count, plus the weight bytes streamed from HBM
    weighted at :data:`GATES_PER_BYTE`.  Lower is better.  On backends that
    interpret Pallas (CPU) the Pallas kernels carry
    :data:`INTERPRET_PENALTY` so the prior reflects wall-clock reality
    there; the autotune cache overrides the prior either way.

    Grouped problems pass the expert count ``e``; ``m`` is then the
    *per-expert* capacity ``C``.  Both terms scale by ``e`` — every expert's
    weights stream every step regardless of how many tokens routed to it, so
    at decode (tiny ``C``) the weight-bytes term dominates exactly as in the
    paper's decode-is-bandwidth-bound regime and the 1.6 b/w grouped paths
    prevail over dense-decoding ones.
    """
    backend = backend or jax.default_backend()
    coeffs = cm.get_coeffs("int8" if act_dtype == "int8" else "fp16")
    compute = float(m) * k * n * spec.prior_per_mac(k, n, coeffs, mu)
    traffic = GATES_PER_BYTE * spec.weight_bytes(k, n, mu)
    cost = (compute + traffic) * (e if e is not None else 1)
    if spec.pallas and backend != "tpu":
        cost *= INTERPRET_PENALTY
    return cost


# ---------------------------------------------------------------------------
# Autotune cache
# ---------------------------------------------------------------------------


def _default_cache_path() -> str:
    return os.environ.get(
        CACHE_PATH_ENV,
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json"))


#: current on-disk schema.  v2 added the grouped (batched-expert) key form
#: ``E<e>:M<C>:K..:N..`` — v1 files hold only dense keys, which are
#: unchanged, so v1 entries load as-is.
CACHE_SCHEMA_VERSION = 2
_COMPATIBLE_SCHEMAS = {1, CACHE_SCHEMA_VERSION}


@dataclass
class AutotuneCache:
    """Disk-persisted measurements: ``(M,K,N,dtype,backend) → {kernel: µs}``,
    grouped problems keyed with their expert count prepended.

    JSON format (schema_version 2)::

        {"schema_version": 2,
         "entries": {"M8:K1024:N512:mu3:float32:cpu": {"ref": 410.2, ...},
                     "E16:M4:K4096:N6400:mu3:bfloat16:tpu": {...}}}

    ``mu`` is part of the key: LUT key-decode cost and bytes streamed scale
    with the group size, so timings at one mu must not steer another.  For
    grouped keys ``M`` is the *per-expert* capacity ``C``.
    """

    path: str = field(default_factory=_default_cache_path)
    entries: dict = field(default_factory=dict)

    @staticmethod
    def key(m: int, k: int, n: int, act_dtype: str, backend: str, *,
            mu: int = 3, e: int | None = None) -> str:
        prefix = f"E{e}:" if e is not None else ""
        return f"{prefix}M{m}:K{k}:N{n}:mu{mu}:{act_dtype}:{backend}"

    @classmethod
    def load(cls, path: str | None = None) -> "AutotuneCache":
        path = path or _default_cache_path()
        entries = {}
        try:
            with open(path) as fh:
                doc = json.load(fh)
            if isinstance(doc, dict) and \
                    doc.get("schema_version") in _COMPATIBLE_SCHEMAS:
                entries = doc.get("entries", {})
        except (OSError, ValueError):
            pass
        return cls(path=path, entries=entries)

    def save(self) -> None:
        """Atomically persist: write a *unique* temp file in the target
        directory, fsync, then ``os.replace``.  Readers never observe a
        partial file (mid-write kill) and concurrent writers (parallel
        ``autotune_sweep.py`` runs) cannot interleave into each other's temp
        file — last replace wins whole."""
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".autotune-", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump({"schema_version": CACHE_SCHEMA_VERSION,
                           "entries": self.entries}, fh,
                          indent=1, sort_keys=True)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def record(self, m: int, k: int, n: int, act_dtype: str, backend: str,
               kernel: str, us: float, *, mu: int = 3,
               e: int | None = None) -> None:
        key = self.key(m, k, n, act_dtype, backend, mu=mu, e=e)
        self.entries.setdefault(key, {})[kernel] = us

    def timings(self, m, k, n, act_dtype, backend, *, mu: int = 3,
                e: int | None = None) -> dict[str, float]:
        return dict(self.entries.get(
            self.key(m, k, n, act_dtype, backend, mu=mu, e=e), {}))

    def best(self, m: int, k: int, n: int, act_dtype: str,
             backend: str, *, mu: int = 3, e: int | None = None) -> str | None:
        t = self.timings(m, k, n, act_dtype, backend, mu=mu, e=e)
        t = {name: us for name, us in t.items() if name in REGISTRY}
        return min(t, key=t.get) if t else None

    def __len__(self) -> int:
        return len(self.entries)


_CACHE: AutotuneCache | None = None


def get_autotune_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = AutotuneCache.load()
    return _CACHE


def reset_autotune_cache() -> None:
    """Drop the in-process cache (re-reads REPRO_AUTOTUNE_CACHE on next use)."""
    global _CACHE
    _CACHE = None


# ---------------------------------------------------------------------------
# Mesh-sharded dispatch: per-shard problem localization
# ---------------------------------------------------------------------------


def _div(dim: int, parts: int) -> int:
    """Per-shard extent of ``dim`` split ``parts``-ways — only when the split
    is even (mirrors ``sharding._validate``: a non-divisible dim falls back
    to replication, so its dispatch extent stays global)."""
    return dim // parts if parts > 1 and dim % parts == 0 else dim


@dataclass(frozen=True)
class ShardInfo:
    """Trace-time mesh geometry for per-shard kernel dispatch.

    Under GSPMD the traced shapes are *global*, but each device executes the
    *local* shard of every matmul — so kernel selection and autotune-cache
    keys must be derived from the per-shard problem, not the global one.
    ``ShardInfo`` translates a global problem to its local shard using the
    same name-based TP/EP rules as ``repro.parallel.sharding``:

      * ``model``: TP degree — out-projection roles (``wq``/``wi``/...)
        shard N, in-projection roles (``wo``/``down``/...) shard K;
      * ``data``:  EP degree — grouped (MoE) problems shard the expert dim;
      * ``batch``: divisor for the dense M dim (batch-sharded activations;
        the engine sets it per entry point, since chunked prefill runs one
        request at a time and must not divide its M = chunk extent).

    An unknown/None role leaves K and N untouched (the weight is replicated).
    Activated via :func:`shard_scope`; dispatch outside any scope is exactly
    the single-device behavior.

    ``n_heads``/``n_kv_heads`` mirror the head-gated attention rule
    (``sharding.param_specs(heads=...)``): qkv projections only shard their
    out dim at whole-head granularity, so when the head count doesn't divide
    ``model`` the weight is replicated and N stays global here too.  Zero
    (the default) disables the gate — legacy flat-dim sharding.
    """

    model: int = 1
    data: int = 1
    batch: int = 1
    n_heads: int = 0
    n_kv_heads: int = 0

    @property
    def partitioned(self) -> bool:
        """True when the step is split over more than one device."""
        return self.model * self.data * self.batch > 1

    def local_dense(self, role: str | None, m: int, k: int, n: int):
        from repro.parallel.sharding import (_NO_TP_ROLES, _SPLIT_ROLES,
                                             TP_IN_ROLES, TP_OUT_ROLES)

        m = _div(m, self.batch)
        if role in TP_OUT_ROLES:
            # partial-replication gate (ssm wz): replicated whenever batch
            # axes coexist with model parallelism — mirrors _param_spec
            if role in _NO_TP_ROLES:
                if self.data == 1:
                    n = _div(n, self.model)
                return m, k, n
            # split-site gate (xlstm ffn_up/up, ssm wx): architecture-
            # constant segment counts, always on — mirrors _param_spec
            seg = _SPLIT_ROLES.get(role)
            if seg is not None:
                if seg % self.model == 0:
                    n = _div(n, self.model)
                return m, k, n
            h = {"wq": self.n_heads, "wk": self.n_kv_heads,
                 "wv": self.n_kv_heads}.get(role, 0)
            if not h or h % self.model == 0:
                n = _div(n, self.model)
        elif role in TP_IN_ROLES:
            # column-parallel packed layout: the decode path's in-projections
            # shard dout (see sharding._IN_MODEL — byte-dim sharding breaks
            # the base-3 unpack's logical-K slice), so the local problem has
            # a full K and an N divided by the TP degree
            n = _div(n, self.model)
        return m, k, n

    def local_grouped(self, role: str | None, e: int, c: int, k: int, n: int):
        """MoE expert stacks: EP shards E on data; inside each expert the
        up-projections (``wi``/``wg``) shard N and the down-projection
        (``wo``/``down``) shards K on model — mirroring the ``moe`` packed
        rules in ``sharding._param_spec``.  Capacity C stays global (token
        routing is not capacity-sharded)."""
        e = _div(e, self.data)
        if role in ("wi", "wg"):
            n = _div(n, self.model)
        elif role in ("wo", "down"):
            k = _div(k, self.model)
        return e, c, k, n


_SHARD_INFO: ShardInfo | None = None


@contextmanager
def shard_scope(info: ShardInfo | None):
    """Activate ``info`` for every dispatch decision made inside the body.

    Entered at *trace* time (the mesh-mode engine wraps its jitted entry
    points) — selection happens while tracing, so the scope never needs to
    survive into compiled execution.  ``None`` is a no-op scope."""
    global _SHARD_INFO
    prev = _SHARD_INFO
    _SHARD_INFO = info
    try:
        yield
    finally:
        _SHARD_INFO = prev


def current_shard_info() -> ShardInfo | None:
    return _SHARD_INFO


def tpu_exclusion(spec: KernelSpec, backend: str) -> str | None:
    """Why ``spec`` cannot run on ``backend`` (``None`` when it can).

    An explicit exclusion, not a try/except fallback: on TPU the kernels
    registered with ``tpu=False`` do not compile with Mosaic, and under a
    partitioned :func:`shard_scope` no Pallas kernel can run, because GSPMD
    cannot partition a Mosaic custom call."""
    if backend != "tpu":
        return None
    if not spec.tpu:
        return "does not compile for TPU"
    if spec.pallas and _SHARD_INFO is not None and _SHARD_INFO.partitioned:
        return "is a Mosaic kernel, which GSPMD cannot partition over a mesh"
    return None


# ---------------------------------------------------------------------------
# Selection + public entry point
# ---------------------------------------------------------------------------


def _act_dtype_name(x: jax.Array) -> str:
    return jnp.dtype(x.dtype).name


def select_kernel(m: int, k: int, n: int, act_dtype: str, *,
                  policy: str | None = None, backend: str | None = None,
                  cache: AutotuneCache | None = None,
                  mu: int = 3, e: int | None = None,
                  role: str | None = None) -> KernelSpec:
    """Resolve a policy to a registered kernel for the given problem.

    Policies:
      * ``"fixed:<name>"`` — always use ``<name>`` (reproducibility pin);
        raises if the kernel does not support the dtype/shape.
      * ``"auto"`` — autotune-cache best if measured, else analytical prior.
      * ``"prior"`` — analytical prior only (ignore the cache).

    ``policy=None`` reads ``$REPRO_TERNARY_POLICY``, defaulting to ``auto``.

    Grouped (batched-expert) problems pass ``e`` (the expert count, with
    ``m`` the per-expert capacity); only grouped kernels are then eligible.
    A ``fixed:<dense-kernel>`` pin resolves through the dense kernel's
    ``grouped_variant`` (``ref → grouped_ref`` etc.) so ONE policy string
    governs a whole model — MoE layers included; pinning a dense kernel with
    no grouped analogue (the LUT/sign-flip paths) raises on MoE problems.

    Under an active :func:`shard_scope`, the problem dims are first mapped
    to their per-device shard via ``role`` (the projection's parameter-leaf
    name, e.g. ``"wq"``/``"wo"``) — cache lookups and the prior then score
    the *local* problem each device actually executes, keyed with the
    unchanged schema-v2 key format at the local dims.
    """
    policy = policy or os.environ.get(DEFAULT_POLICY_ENV, "auto")
    backend = backend or jax.default_backend()
    info = _SHARD_INFO
    if info is not None:
        if e is not None:
            e, m, k, n = info.local_grouped(role, e, m, k, n)
        else:
            m, k, n = info.local_dense(role, m, k, n)

    if policy.startswith("fixed:"):
        spec = get_kernel(policy[len("fixed:"):])
        if e is not None and not spec.grouped:
            if spec.grouped_variant is None:
                raise ValueError(
                    f"kernel {spec.name!r} has no grouped (batched-expert) "
                    f"variant; MoE expert matmuls cannot honour policy "
                    f"'fixed:{spec.name}'. Pin one of "
                    f"{sorted(s.name for s in REGISTRY.values() if s.grouped)}"
                    f" or a dense kernel with a grouped analogue "
                    f"{sorted(s.name for s in REGISTRY.values() if s.grouped_variant)}")
            spec = get_kernel(spec.grouped_variant)
        if not spec.supports(m, k, n, act_dtype, e):
            raise ValueError(
                f"kernel {spec.name!r} does not support M={m} K={k} N={n} "
                f"E={e} act_dtype={act_dtype} (supported dtypes: "
                f"{sorted(spec.act_dtypes)}; grouped={spec.grouped})")
        why = tpu_exclusion(spec, backend)
        if why is not None:
            raise ValueError(f"kernel {spec.name!r} {why}; policy "
                             f"'fixed:{spec.name}' cannot run on {backend}")
        return spec

    if policy not in ("auto", "prior"):
        raise ValueError(
            f"unknown policy {policy!r}; expected 'auto', 'prior', or "
            f"'fixed:<name>' with name in {sorted(REGISTRY)}")

    candidates = eligible_kernels(m, k, n, act_dtype, e, backend)
    if not candidates:
        raise ValueError(f"no registered kernel supports M={m} K={k} N={n} "
                         f"E={e} act_dtype={act_dtype} on {backend}")

    if policy == "auto":
        cache = cache or get_autotune_cache()
        best = cache.best(m, k, n, act_dtype, backend, mu=mu, e=e)
        if best is not None and get_kernel(best) in candidates:
            return get_kernel(best)

    # equal priors go to the kernel specialised for fewer activation dtypes
    # (w2a8's integer MXU path over dequant_packed's f32 one on int8); the
    # name tiebreak keeps selection deterministic across dict orderings
    return min(candidates,
               key=lambda s: (static_prior(s, m, k, n, act_dtype, backend,
                                           mu, e),
                              len(s.act_dtypes), s.name))


def ternary_matmul(x: jax.Array, w, *, scale=None, policy: str | None = None,
                   mu: int | None = None, interpret: bool | None = None,
                   backend: str | None = None,
                   cache: AutotuneCache | None = None,
                   role: str | None = None) -> jax.Array:
    """``y[..., n] = Σ_k x[..., k] · trits(w)[n, k] · scale`` via the best
    registered kernel for this (shape, dtype, backend).

    Args:
      x: ``[..., K]`` activations — float (fp32/bf16/fp16) or pre-quantized
        int8 (routes the W1.58A8 paths; caller applies the activation scale).
      w: :class:`TernaryWeight`, :class:`repro.core.encoding.PackedTernary`,
        or an int8 trit matrix ``[N, K]``.
      scale: overrides ``w``'s weight scale (rank-1 correction, applied once).
      policy: ``"auto"`` | ``"prior"`` | ``"fixed:<name>"``; ``None`` reads
        ``$REPRO_TERNARY_POLICY`` (default ``auto``).
      mu: LUT group size override (default: the weight's, typically 3).
      interpret: run Pallas kernels in interpret mode; ``None`` (default)
        resolves from the executing backend — compiled on real TPU,
        interpret everywhere else.
      role: the projection's parameter-leaf name (``"wq"``, ``"wo"``, ...);
        only consulted under an active :func:`shard_scope`, where it decides
        which dim the TP axis shards so dispatch keys on the local problem.

    Returns ``[..., N]`` in ``x``'s dtype (float inputs) or float32 (int8
    inputs).  Selection happens at Python/trace time from *static* shapes, so
    the call is jit-compatible; under jit the choice is frozen into the
    compiled executable.
    """
    tw = _as_weight(w, scale, mu)
    mu = mu or tw.mu
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m, k = int(np.prod(lead)) if lead else 1, x.shape[-1]
    if k != tw.in_features:
        raise ValueError(f"x K={k} != weight K={tw.in_features}")
    n = tw.out_features
    act = _act_dtype_name(x)

    spec = select_kernel(m, k, n, act, policy=policy, backend=backend,
                         cache=cache, mu=mu, role=role)
    y = spec.run(x2, tw, mu, interpret)
    s = tw.scale if scale is None else scale
    if s is not None:
        y = y * jnp.asarray(s, jnp.float32)
    out_dtype = jnp.float32 if act == "int8" else x.dtype
    return y.reshape(*lead, n).astype(out_dtype)


def grouped_ternary_matmul(x: jax.Array, w, *, scale=None,
                           policy: str | None = None, mu: int | None = None,
                           interpret: bool | None = None,
                           backend: str | None = None,
                           cache: AutotuneCache | None = None,
                           role: str | None = None) -> jax.Array:
    """``y[e, ..., n] = Σ_k x[e, ..., k] · trits(w)[e, n, k] · scale[e]`` —
    the batched-expert (MoE) entry point of the dispatch layer.

    Args:
      x: ``[E, ..., K]`` per-expert activation rows (MoE dispatch buffers
        ``[E, C, K]``) — float, or pre-quantized int8 for the W1.58A8 path.
      w: :class:`GroupedTernaryWeight` or stacked int8 trits ``[E, N, K]``.
      scale: overrides ``w``'s per-expert scale ``[E]`` (rank-1, applied
        once on the way out).
      policy / mu / interpret / backend / cache / role: as
        :func:`ternary_matmul`; ``fixed:<dense>`` pins map through the dense
        kernel's grouped variant so one policy string governs dense and MoE
        layers alike, and ``role`` (under a :func:`shard_scope`) localizes
        the EP-sharded expert dim and the TP-sharded K or N.

    Returns ``[E, ..., N]`` in ``x``'s dtype (float in) or float32 (int8
    in).  Selection is static-shape/trace-time, keyed on
    ``(E, C, K, N, dtype, backend)``.
    """
    gw = _as_grouped_weight(w, scale, mu)
    mu = mu or gw.mu
    if x.ndim < 2 or x.shape[0] != gw.n_experts:
        raise ValueError(
            f"grouped activations must be [E, ..., K] with E="
            f"{gw.n_experts}; got shape {x.shape}")
    lead = x.shape[1:-1]
    k = x.shape[-1]
    if k != gw.in_features:
        raise ValueError(f"x K={k} != weight K={gw.in_features}")
    E, n = gw.n_experts, gw.out_features
    x3 = x.reshape(E, -1, k)
    c = int(np.prod(lead)) if lead else 1
    act = _act_dtype_name(x)

    spec = select_kernel(c, k, n, act, policy=policy, backend=backend,
                         cache=cache, mu=mu, e=E, role=role)
    y = spec.run(x3, gw, mu, interpret)
    s = gw.scale if scale is None else scale
    if s is not None:
        s = jnp.asarray(s, jnp.float32)
        y = y * (s.reshape(E, *([1] * (y.ndim - 1))) if s.ndim else s)
    out_dtype = jnp.float32 if act == "int8" else x.dtype
    return y.reshape(E, *lead, n).astype(out_dtype)


# ---------------------------------------------------------------------------
# Autotuning
# ---------------------------------------------------------------------------


def autotune(m: int, k: int, n: int, act_dtype: str = "float32", *,
             kernels: list[str] | None = None, reps: int = 3, seed: int = 0,
             interpret: bool | None = None, backend: str | None = None,
             cache: AutotuneCache | None = None, save: bool = True,
             mu: int = 3, e: int | None = None) -> dict[str, float]:
    """Benchmark every eligible kernel on an ``[m,k]×[n,k]`` problem and
    record the wall-times (µs) in the autotune cache.

    Timing reproduces the serving data path: the 1.6 b/w packed artifact
    enters the jitted function as an *argument*, so kernels that derive
    trits/keys (ref, signflip, lut_*) pay that per-step decode inside the
    measurement, exactly as ``layers.linear`` does — not from baked-in
    constants, which would bias selection against the in-kernel-decode paths.

    Pass ``e`` to tune a grouped (batched-expert) problem: ``m`` is then the
    per-expert capacity ``C``, operands are stacked ``[e, m, k]`` acts ×
    ``[e, n, ceil(k/5)]`` packed, and only grouped kernels run.

    A kernel that fails is skipped with a warning off TPU (a backend it was
    not written for); on TPU, where only kernels that compile there are
    eligible, the failure is raised.

    Returns ``{kernel_name: µs}``.  Subsequent ``policy="auto"`` dispatches
    for the same ``(M, K, N, dtype, backend)`` (+ ``E`` if grouped) use the
    measured best.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    local = jax.default_backend()
    backend = backend or local
    if backend != local:
        # timings are taken on the local device; recording them under another
        # backend's cache key would poison that backend's auto dispatch
        raise ValueError(f"autotune measures on the local backend {local!r}; "
                         f"cannot record for backend={backend!r}")
    cache = cache or get_autotune_cache()
    rng = np.random.default_rng(seed)
    lead = () if e is None else (e,)
    if act_dtype == "int8":
        x = jnp.asarray(rng.integers(-127, 128, size=(*lead, m, k)), jnp.int8)
    else:
        x = jnp.asarray(rng.normal(size=(*lead, m, k)), act_dtype)
    packed = encoding.pack_base3(
        jnp.asarray(rng.integers(-1, 2, size=(*lead, n, k)), jnp.int8))

    names = kernels or [s.name for s in
                        eligible_kernels(m, k, n, act_dtype, e, backend)]
    results: dict[str, float] = {}
    for name in names:
        spec = get_kernel(name)
        if not spec.supports(m, k, n, act_dtype, e):
            continue

        def call(xx, pk, run=spec.run):
            cls = TernaryWeight if e is None else GroupedTernaryWeight
            return run(xx, cls.from_packed(pk, 1.0, k, mu=mu), mu, interpret)

        fn = jax.jit(call)
        try:
            jax.block_until_ready(fn(x, packed))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(reps):
                y = fn(x, packed)
            jax.block_until_ready(y)
            us = (time.perf_counter() - t0) / reps * 1e6
        except Exception as exc:
            if backend == "tpu":
                # every kernel eligible on TPU must compile and run there; a
                # failure is a bug to surface, not a timing to skip
                raise
            tag = f"E{e} " if e is not None else ""
            warnings.warn(f"autotune: kernel {name!r} failed on "
                          f"{tag}M{m} K{k} N{n} {act_dtype}/{backend}: {exc}")
            continue
        results[name] = us
        cache.record(m, k, n, act_dtype, backend, name, us, mu=mu, e=e)
    if save and results:
        cache.save()
    return results
