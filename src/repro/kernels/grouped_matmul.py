"""Pallas TPU kernels: grouped (batched-expert) ternary matmuls.

The MoE datapath the paper's bandwidth math requires: expert weights stay in
HBM as stacked base-3 packed bytes ``[E, O, ceil(N/5)]`` (1.6 b/w) and every
expert's tile is expanded to trits **in VMEM** right before its MXU
contraction — the grid gains a leading expert dimension, so one kernel launch
covers the whole expert stack without ever materializing a dense
``[E, O, N]`` weight tensor.

Two variants mirror the dense kernel family:

  * :func:`grouped_packed_matmul` — float activations (bf16/f32 serving
    path), f32 accumulation: the grouped analogue of
    ``dequant_matmul.packed_matmul``;
  * :func:`grouped_w2a8_matmul` — pre-quantized int8 activations, exact
    int32 accumulation: the grouped analogue of ``w2a8_matmul`` (the paper's
    Table-I W1.58A8 operating point, per expert).

Per-expert absmean scales are a rank-1 correction applied by the caller on
the way out (``y * scale[:, None, None]``), same convention as the dense
kernels.  Decode-time expert capacity ``C`` is tiny (often 1), so the
activation block is padded up — the launch stays profitable because the win
is streamed weight bytes, not MACs.

Under a serving mesh the expert stack is expert-parallel on the ``data``
axis and each expert's matmul tensor-parallel on ``model`` (wi/wg shard N,
wo shards K — rules in ``repro/parallel/sharding.py``); dispatch sees the
**per-shard** problem (local ``E``/``K``/``N`` via
``kernels.dispatch.ShardInfo.local_grouped``), so autotune cache keys and
backend choice follow what each device actually runs, not the global shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.encoding import TRITS_PER_BYTE
from repro.kernels.backend import resolve_interpret
from repro.kernels.dequant_matmul import block_bytes, digit_planes, plane_matmul


def _grouped_kernel(x_ref, p_ref, out_ref):
    """x_ref [1, 5, bc, bnb]; p_ref [1, bo, bnb] uint8; out [1, bc, bo]."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += plane_matmul(lambda i: x_ref[0, i], p_ref[0],
                               out_ref.dtype)


def _pad_and_call(x, packed, *, block_c, block_o, block_n, interpret,
                  acc_dtype):
    """Shared pad-to-blocks + pallas_call for both grouped variants.

    x: [E, C, N]; packed: [E, O, NB].  Returns [E, C, O] acc_dtype.
    Padding and the digit-plane layout follow the dense kernels
    (``dequant_matmul.packed_call``): pad bytes meet zero activations, and
    padded C/O rows are sliced off after the call.
    """
    E, C, _ = x.shape
    _, O, NB = packed.shape
    bnb = block_bytes(block_n, NB)
    block_c = min(block_c, C)
    block_o = min(block_o, O)
    pad_c, pad_o, pad_nb = (-C) % block_c, (-O) % block_o, (-NB) % bnb
    planes = digit_planes(x, NB)                       # [E, 5, C, NB]
    if pad_c or pad_nb:
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, pad_c), (0, pad_nb)))
    if pad_o or pad_nb:
        packed = jnp.pad(packed, ((0, 0), (0, pad_o), (0, pad_nb)))
    Cp, Op, NBp = C + pad_c, O + pad_o, NB + pad_nb

    out = pl.pallas_call(
        _grouped_kernel,
        grid=(E, Cp // block_c, Op // block_o, NBp // bnb),
        in_specs=[
            pl.BlockSpec((1, TRITS_PER_BYTE, block_c, bnb),
                         lambda e, i, j, k: (e, 0, i, k)),
            pl.BlockSpec((1, block_o, bnb), lambda e, i, j, k: (e, j, k)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_o),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Op), acc_dtype),
        interpret=resolve_interpret(interpret),
    )(planes, packed)
    return out[:, :C, :O]


@functools.partial(
    jax.jit,
    static_argnames=("n", "block_c", "block_o", "block_n", "interpret"))
def grouped_packed_matmul(
    x: jax.Array,
    packed: jax.Array,
    n: int,
    *,
    block_c: int = 8,
    block_o: int = 128,
    block_n: int = 640,  # 128 packed bytes: one lane-width of each plane
    interpret: bool | None = None,
) -> jax.Array:
    """y[e, c, o] = Σ_n x[e, c, n] · unpack(packed[e])[o, n] (f32).

    Args:
      x:      [E, C, N] float activations (per-expert capacity rows).
      packed: [E, O, ceil(N/5)] stacked base-3 packed ternary weights (the
        byte dim may carry alignment padding past ``ceil(n/5)``).
      n:      logical N (columns beyond n are zero by construction).
    """
    if x.shape[0] != packed.shape[0]:
        raise ValueError(f"expert dims differ: x {x.shape} vs packed "
                         f"{packed.shape}")
    if x.shape[-1] < n or packed.shape[-1] * TRITS_PER_BYTE < n:
        raise ValueError((x.shape, packed.shape, n))
    return _pad_and_call(x, packed, block_c=block_c, block_o=block_o,
                         block_n=block_n, interpret=interpret,
                         acc_dtype=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("n", "block_c", "block_o", "block_n", "interpret"))
def grouped_w2a8_matmul(
    x_q: jax.Array,
    packed: jax.Array,
    n: int,
    *,
    block_c: int = 8,
    block_o: int = 128,
    block_n: int = 640,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact int32 y[e, c, o] = Σ_n x_q[e, c, n] · trits(packed[e])[o, n].

    x_q: [E, C, N] int8 (per-token quantized activations, routed per expert).
    packed: [E, O, ceil(N/5)] stacked base-3 ternary weights.
    """
    if x_q.shape[0] != packed.shape[0]:
        raise ValueError(f"expert dims differ: x {x_q.shape} vs packed "
                         f"{packed.shape}")
    if x_q.shape[-1] < n or packed.shape[-1] * TRITS_PER_BYTE < n:
        raise ValueError((x_q.shape, packed.shape, n))
    return _pad_and_call(x_q, packed, block_c=block_c, block_o=block_o,
                         block_n=block_n, interpret=interpret,
                         acc_dtype=jnp.int32)
