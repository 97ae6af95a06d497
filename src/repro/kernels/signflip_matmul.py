"""Pallas TPU kernel for the sign-flip ternary matmul baseline (Fig. 1 middle).

The ASIC baseline replaces each multiplier with a 3:1 mux selecting
``{+x, -x, 0}``.  The TPU-native equivalent decomposes the ternary matrix into
its two binary indicator planes and rides the MXU:

    y = x @ [w == +1]ᵀ  -  x @ [w == -1]ᵀ

i.e. two binary-mask matmuls — every "multiplication" is a conditional add,
exactly the baseline's arithmetic, but systolic.  The indicator construction
happens in VMEM on the VPU; weights stream as int8 trits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _signflip_kernel(x_ref, w_ref, out_ref):
    """x_ref [bb, bn] float; w_ref [bo, bn] int8 trits; out_ref [bb, bo] f32."""
    k = pl.program_id(2)
    x = x_ref[...]
    w = w_ref[...]
    pos = (w == 1).astype(x.dtype)
    neg = (w == -1).astype(x.dtype)
    partial = jax.lax.dot_general(
        x, pos, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) - jax.lax.dot_general(
        x, neg, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_o", "block_n", "interpret")
)
def signflip_matmul(
    x: jax.Array,
    w_t: jax.Array,
    *,
    block_b: int = 8,
    block_o: int = 128,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """y[b, o] = Σ_n x[b, n]·w_t[o, n] with w_t ∈ {-1,0,1} (int8), no multiplies."""
    B, N = x.shape
    O, N2 = w_t.shape
    assert N == N2, (N, N2)
    block_b = min(block_b, B)
    block_o = min(block_o, O)
    block_n = min(block_n, N)
    pad_b = (-B) % block_b
    pad_o = (-O) % block_o
    pad_n = (-N) % block_n
    if pad_b or pad_n:
        x = jnp.pad(x, ((0, pad_b), (0, pad_n)))
    if pad_o or pad_n:
        w_t = jnp.pad(w_t, ((0, pad_o), (0, pad_n)))
    Bp, Op, Np = B + pad_b, O + pad_o, N + pad_n

    out = pl.pallas_call(
        _signflip_kernel,
        grid=(Bp // block_b, Op // block_o, Np // block_n),
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_o, block_n), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Op), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, w_t)
    return out[:B, :O]
