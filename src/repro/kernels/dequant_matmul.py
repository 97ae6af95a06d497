"""Pallas TPU kernel: packed-ternary dequantize + matmul (deployment path).

This is the kernel that realizes the paper's *bandwidth* win on TPU: weights
stream from HBM as base-3-packed uint8 (5 trits/byte = 1.6 bits/weight, the
paper's §III-D density) and are expanded to the activation dtype **in VMEM**,
then contracted on the MXU.  HBM traffic for weights drops 10× vs bf16 and
20% vs naive 2-bit packing — exactly the decode-stage bottleneck the paper
attacks.

Decode uses arithmetic base-3 digit extraction (5 div-mod-3 steps on the VPU)
rather than a table gather, and never interleaves the digits back into
weight order: byte ``j`` of a row holds the trits of weight columns
``5j .. 5j+4``, so base-3 digit ``i`` of a ``[bo, bnb]`` byte tile is itself a
``[bo, bnb]`` trit *plane* covering columns ``5j + i``.  The activations are
permuted once outside the kernel into the matching digit-major layout
(:func:`digit_planes`), and the tile's product is five lane-aligned MXU
contractions, one per plane.  Every in-kernel value keeps the byte tile's
``(sublane, lane)`` shape — no minor dim of 5, no shape cast — which is what
lets Mosaic compile the kernel at real model widths.

Float activations accumulate in f32; int8 activations accumulate exactly in
int32 on the MXU's integer path (:mod:`repro.kernels.w2a8_matmul`).

The grid is ``(row blocks, O/bo, NB/bnb)``, row blocks outermost, so every
row block streams the whole packed matrix from HBM and expands it again.  A
call of up to :data:`ROW_BLOCK_CAP` rows — a decode step over the slots, a
prefill chunk — is therefore one row block (:func:`row_blocks`): each packed
tile is DMA'd and expanded once per call, whatever the call's row count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.encoding import TRITS_PER_BYTE
from repro.kernels.backend import resolve_interpret


def digit_planes(x: jax.Array, nb: int) -> jax.Array:
    """``[..., R, N]`` activations → ``[..., 5, R, nb]`` digit-major planes.

    Plane ``i`` holds activation columns ``5j + i`` (``j < nb``), the columns
    that meet base-3 digit ``i`` of packed byte ``j``.  ``N`` is zero-padded
    to ``5 * nb`` first, so padded trits always meet zero activations."""
    *lead, r, n = x.shape
    full = nb * TRITS_PER_BYTE
    if n < full:
        x = jnp.pad(x, [(0, 0)] * (len(lead) + 1) + [(0, full - n)])
    x = x.reshape(*lead, r, nb, TRITS_PER_BYTE)
    return jnp.moveaxis(x, -1, -3)


def plane_matmul(plane, p: jax.Array, acc_dtype) -> jax.Array:
    """``Σ_i plane(i) · trits_i(p)ᵀ`` for one tile.

    ``plane(i)`` loads the ``[bb, bnb]`` activation plane ``i``; ``p`` is the
    ``[bo, bnb]`` uint8 byte tile.  Returns ``[bb, bo]`` in ``acc_dtype``."""
    v = p.astype(jnp.int32)
    acc = None
    for i in range(TRITS_PER_BYTE):
        x = plane(i)
        w = (v % 3 - 1).astype(x.dtype)
        v = v // 3
        part = jax.lax.dot_general(
            x, w, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype)
        acc = part if acc is None else acc + part
    return acc


def _dense_kernel(x_ref, p_ref, out_ref):
    """x_ref [5, bb, bnb]; p_ref [bo, bnb] uint8; out [bb, bo] (f32/int32)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += plane_matmul(lambda i: x_ref[i], p_ref[...],
                                 out_ref.dtype)


#: Most rows in one row block.  VMEM one grid step holds at 256 rows,
#: ``block_o`` 128 and ``bnb`` 128 bytes, inputs and output double-buffered:
#: activation planes 2 x 5 x 256 x 128 x (1 B int8 | 2 B bf16 | 4 B f32) =
#: 320 KiB | 640 KiB | 1.25 MiB; packed tile 2 x 128 x 128 B = 32 KiB;
#: output tile 2 x 256 x 128 x 4 B = 256 KiB; int32 digit tile and trit tile
#: 2 x 128 x 128 x 4 B = 128 KiB; one plane's product 256 x 128 x 4 B =
#: 128 KiB.  At most ~1.8 MiB (f32), far inside v5e's 16 MiB default scoped
#: VMEM; ``tests/test_tpu_compile.py`` compiles a block of this size.
ROW_BLOCK_CAP = 256


def row_blocks(rows: int, block_b: int | None = None) -> tuple[int, int]:
    """``(rows per block, number of row blocks)`` for a call of ``rows``.

    ``block_b=None`` takes every row into one block up to
    :data:`ROW_BLOCK_CAP` (a block equal to the full row dimension is always
    legal for Mosaic, so no alignment padding) and tiles at the cap above
    it; an explicit ``block_b`` tiles at ``min(block_b, rows)``.  Rows are
    padded to ``block * count``."""
    block = min(rows, ROW_BLOCK_CAP if block_b is None else block_b)
    return block, -(-rows // block)


def block_bytes(block_n: int, nb: int) -> int:
    """Packed bytes per reduction step: ``block_n`` trits, capped at the
    row's ``nb`` bytes (a block equal to the full dim is always legal)."""
    return max(1, min(block_n // TRITS_PER_BYTE, nb))


def packed_call(x, packed, n, *, block_b, block_o, block_n, interpret,
                acc_dtype):
    """Pad to blocks, permute to digit planes, and launch the dense kernel.

    x: [B, N] activations; packed: [O, NB] base-3 bytes (NB may carry
    alignment padding past ``ceil(n/5)``).  Returns [B, O] ``acc_dtype``.
    Rows are tiled by :func:`row_blocks` (``block_b=None``: one block for
    up to :data:`ROW_BLOCK_CAP` rows).  Pad *bytes* decode to -1 trits but
    meet zero activations, so their products vanish; padded B/O rows are
    sliced off after the call."""
    B, N = x.shape
    O, NB = packed.shape
    if N < n or NB * TRITS_PER_BYTE < n:
        raise ValueError((N, NB, n))
    bnb = block_bytes(block_n, NB)
    block_b, n_row_blocks = row_blocks(B, block_b)
    block_o = min(block_o, O)
    pad_b = block_b * n_row_blocks - B
    pad_o, pad_nb = (-O) % block_o, (-NB) % bnb
    planes = digit_planes(x, NB)                       # [5, B, NB]
    if pad_b or pad_nb:
        planes = jnp.pad(planes, ((0, 0), (0, pad_b), (0, pad_nb)))
    if pad_o or pad_nb:
        packed = jnp.pad(packed, ((0, pad_o), (0, pad_nb)))
    Bp, Op, NBp = B + pad_b, O + pad_o, NB + pad_nb

    out = pl.pallas_call(
        _dense_kernel,
        grid=(Bp // block_b, Op // block_o, NBp // bnb),
        in_specs=[
            pl.BlockSpec((TRITS_PER_BYTE, block_b, bnb),
                         lambda i, j, k: (0, i, k)),
            pl.BlockSpec((block_o, bnb), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Op), acc_dtype),
        interpret=resolve_interpret(interpret),
    )(planes, packed)
    return out[:B, :O]


@functools.partial(
    jax.jit, static_argnames=("n", "block_b", "block_o", "block_n", "interpret")
)
def packed_matmul(
    x: jax.Array,
    packed: jax.Array,
    n: int,
    *,
    block_b: int | None = None,
    block_o: int = 128,
    block_n: int = 640,  # 128 packed bytes: one lane-width of each plane
    interpret: bool | None = None,
) -> jax.Array:
    """y[b, o] = Σ_n x[b, n] · unpack(packed)[o, n], accumulated in f32.

    Rows run as one block up to :data:`ROW_BLOCK_CAP` (``block_b=None``),
    so each packed tile is streamed and expanded once per call; an explicit
    ``block_b`` tiles rows at that size.

    Args:
      x:      [B, N] float activations (N may include padding up to 5·packed
              cols).
      packed: [O, ceil(N/5)] base-3 packed ternary weights.
      n:      logical N (unpacked columns beyond n are zero by construction).
      interpret: Pallas interpret mode; ``None`` compiles on TPU and
              interprets elsewhere.
    """
    return packed_call(x, packed, n, block_b=block_b, block_o=block_o,
                       block_n=block_n, interpret=interpret,
                       acc_dtype=jnp.float32)
