"""Pallas TPU kernel: W1.58A8 matmul — the paper's Table-I operating point.

BitNet b1.58 runs ternary weights against **INT8 activations**; the
accelerator's INT8 column in the cost model is exactly this datapath.  On
TPU the analogue is an int8×int8→int32 MXU contraction:

  * activations arrive as int8 with a per-row (per-token) fp scale,
  * weights stream as base-3 packed uint8 (1.6 b/w) and are expanded to int8
    trits in VMEM,
  * accumulation is exact int32 (the ASIC's wide accumulators); the two
    scales are applied as a rank-1 correction on the way out.

Against the bf16 dequant path this halves activation bytes and keeps the
MXU in its highest-throughput int8 mode — the TPU-native version of the
paper's "INT8 activations make the arithmetic cheap" observation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dequant_matmul import packed_call


@functools.partial(
    jax.jit, static_argnames=("n", "block_b", "block_o", "block_n", "interpret")
)
def w2a8_matmul(
    x_q: jax.Array,
    packed: jax.Array,
    n: int,
    *,
    block_b: int = 8,
    block_o: int = 128,
    block_n: int = 640,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact int32 y[b,o] = Σ_n x_q[b,n] · trits(packed)[o,n].

    x_q: [B, N] int8 (per-token quantized activations).
    packed: [O, ceil(N/5)] base-3 ternary weights.

    The packed-streaming kernel of :mod:`repro.kernels.dequant_matmul` with
    trits expanded to int8 and an int32 accumulator.
    """
    if x_q.dtype != jnp.int8:
        raise TypeError(f"w2a8_matmul needs int8 activations, got {x_q.dtype}")
    return packed_call(x_q, packed, n, block_b=block_b, block_o=block_o,
                       block_n=block_n, interpret=interpret,
                       acc_dtype=jnp.int32)


def w2a8_linear(x: jax.Array, packed: jax.Array, w_scale: jax.Array, n: int,
                *, interpret: bool | None = None) -> jax.Array:
    """Full W1.58A8 linear: quantize acts → int kernel → rank-1 rescale."""
    from repro.core.quantization import quantize_activations_int8

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_q, x_scale = quantize_activations_int8(x2)
    y = w2a8_matmul(x_q, packed, n, interpret=interpret)
    y = y.astype(jnp.float32) * x_scale * jnp.asarray(w_scale, jnp.float32)
    return y.reshape(*lead, -1).astype(x.dtype)
