"""TL2-style two-trit LUT matmul: 9-entry tables, base-9 packed weights.

The bitnet.cpp TL2 typology (and T-MAC's LUT-centric mpGEMM) groups ternary
weights in *pairs*: a pair of trits has 9 states, so a per-pair activation
table ``T[g] = [x0·t0 + x1·t1 for (t0, t1) in {-1,0,1}²]`` has only 9 entries
and the fetch is a 9-way select — much smaller build cost than the base-3
mu-group encoding's ``(3^mu-1)/2`` entries, at the same storage density:

  * pair → base-9 digit ``d = (t0+1)·3 + (t1+1) ∈ [0, 9)``;
  * 5 digits pack into one uint16 (``9^5 = 59049 ≤ 65536``) → 16 bits per
    10 trits = **1.6 bits/weight exactly**, matching base-3's 5-trits/byte.

Two variants share the packing:

  * :func:`tl2_matmul_ref` — pure-XLA: pair-table build as one dense
    contraction against the [9, 2] combo matrix, one-hot fetch contraction
    (gather-free, MXU/XLA friendly).
  * :func:`tl2_matmul` — Pallas grid kernel mirroring ``lut_matmul``'s
    structure: in-kernel uint16 → digit decode (5 div-mod-9 VPU steps), in-
    kernel iota-synthesized combo matrix, one-hot fetch on the MXU, output-
    stationary VMEM accumulator over the reduction grid dim.

All math runs in f32; int8 activations cast losslessly, and because every
intermediate is integral (|pair sum| ≤ 254, products < 2^24 at practical K)
the int8 path is bit-exact against the dense trit reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.encoding import TRITS_PER_BYTE, unpack_base3
from repro.kernels.backend import resolve_interpret

#: base-9 digits per packed uint16 word
PAIRS_PER_WORD = 5
#: trits per packed uint16 word → 16 / 10 = 1.6 bits per weight
TRITS_PER_WORD = 2 * PAIRS_PER_WORD


def tl2_bits_per_weight() -> float:
    return 16.0 / TRITS_PER_WORD


def pack_tl2(w_t: jax.Array) -> jax.Array:
    """Pack ternary {-1,0,1} → uint16, 10 trits (5 pairs) per word.

    The last axis is zero-padded to a multiple of 10; zero trits map to the
    pair digit 4, whose table entry is identically 0, so padded columns are
    inert in every fetch path.
    """
    *lead, N = w_t.shape
    pad = (-N) % TRITS_PER_WORD
    if pad:
        w_t = jnp.pad(w_t, [(0, 0)] * len(lead) + [(0, pad)])
    pairs = w_t.reshape(*lead, -1, 2).astype(jnp.int32) + 1
    digits = pairs[..., 0] * 3 + pairs[..., 1]          # [..., G] ∈ [0, 9)
    grp = digits.reshape(*lead, -1, PAIRS_PER_WORD)
    powers = jnp.asarray([9**i for i in range(PAIRS_PER_WORD)], jnp.int32)
    return jnp.sum(grp * powers, axis=-1).astype(jnp.uint16)


def repack_base3_to_tl2(packed: jax.Array, n: int) -> jax.Array:
    """Base-3 packed bytes ``[..., ceil(n/5)]`` → TL2 words
    ``[..., ceil(n/10)]`` — the serving-artifact repack (deployment checkpoints
    store base-3; the TL2 kernels re-encode once at load/first-use)."""
    return pack_tl2(unpack_base3(packed, n))


def unpack_tl2_digits(words: jax.Array) -> jax.Array:
    """uint16 [..., W] → base-9 pair digits int32 [..., W*5]."""
    v = words.astype(jnp.int32)
    digs = []
    for _ in range(PAIRS_PER_WORD):
        digs.append(v % 9)
        v = v // 9
    return jnp.stack(digs, axis=-1).reshape(*words.shape[:-1], -1)


def unpack_tl2(words: jax.Array, n: int, dtype=jnp.int8) -> jax.Array:
    """uint16 [..., ceil(n/10)] → trits [..., n] in ``dtype``."""
    d = unpack_tl2_digits(words)
    trits = jnp.stack([d // 3 - 1, d % 3 - 1], axis=-1)
    return trits.reshape(*words.shape[:-1], -1)[..., :n].astype(dtype)


@functools.lru_cache(maxsize=None)
def _combo9_np() -> np.ndarray:
    """[9, 2] int8: row d = the trit pair encoded by base-9 digit d."""
    d = np.arange(9, dtype=np.int64)
    return np.stack([d // 3 - 1, d % 3 - 1], axis=1).astype(np.int8)


def _pair_tables(x: jax.Array) -> jax.Array:
    """[B, G*2] f32 activations → [B, G, 9] per-pair tables (build phase)."""
    B = x.shape[0]
    xg = x.reshape(B, -1, 2)
    C9 = jnp.asarray(_combo9_np(), x.dtype)
    return jax.lax.dot_general(
        xg, C9, dimension_numbers=(((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [B, G, 9]


@functools.partial(jax.jit, static_argnames=("n",))
def tl2_matmul_ref(x: jax.Array, words: jax.Array, n: int) -> jax.Array:
    """Pure-XLA TL2 matmul: ``y[b, o] = Σ_k x[b, k] · trits(words)[o, k]``.

    x:     [B, N'] f32/bf16/int8 activations with N' ≥ n padded to the full
           unpacked width ``words.shape[1] * 10`` (callers zero-pad).
    words: [O, W] uint16 TL2-packed weights.
    """
    B = x.shape[0]
    O, W = words.shape
    full = W * TRITS_PER_WORD
    if x.shape[1] < full:
        x = jnp.pad(x, ((0, 0), (0, full - x.shape[1])))
    tables = _pair_tables(x.astype(jnp.float32))        # [B, G, 9]
    digits = unpack_tl2_digits(words)                   # [O, G]
    iota = jax.lax.broadcasted_iota(jnp.int32, (*digits.shape, 9), 2)
    oh = (iota == digits[..., None]).astype(jnp.float32)  # [O, G, 9]
    return jax.lax.dot_general(
        tables, oh, dimension_numbers=(((1, 2), (1, 2)), ((), ())),
        preferred_element_type=jnp.float32)             # [B, O]


def _tl2_kernel(x_ref, w_ref, out_ref):
    """One (bb, bo) output tile, one bw-word reduction step.

    x_ref:  [bb, bw*10] f32 activation slice
    w_ref:  [bo, bw]    uint16 TL2 words
    out_ref:[bb, bo]    f32 accumulator
    """
    k = pl.program_id(2)
    bb = x_ref.shape[0]

    # ---- Build phase: per-pair 9-entry tables on the MXU.  The [9, 2]
    # combo matrix is synthesized from iota arithmetic (Pallas kernels
    # cannot capture array constants): row d = (d//3 - 1, d%3 - 1).
    di = jax.lax.broadcasted_iota(jnp.int32, (9, 2), 0)
    pj = jax.lax.broadcasted_iota(jnp.int32, (9, 2), 1)
    C9 = jnp.where(pj == 0, di // 3 - 1, di % 3 - 1)
    xg = x_ref[...].reshape(bb, -1, 2)
    tables = jax.lax.dot_general(
        xg, C9.astype(xg.dtype),
        dimension_numbers=(((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # [bb, bg, 9]

    # ---- Fetch phase: decode words → digits (5 div-mod-9 VPU steps), then
    # a one-hot contraction pulls entry d[o, g] from tables[b, g, :].
    v = w_ref[...].astype(jnp.int32)                    # [bo, bw]
    digs = []
    for _ in range(PAIRS_PER_WORD):
        digs.append(v % 9)
        v = v // 9
    digits = jnp.stack(digs, axis=-1).reshape(w_ref.shape[0], -1)  # [bo, bg]
    iota = jax.lax.broadcasted_iota(jnp.int32, (*digits.shape, 9), 2)
    oh = jnp.where(iota == digits[..., None], 1.0, 0.0)
    partial = jax.lax.dot_general(
        tables, oh, dimension_numbers=(((1, 2), (1, 2)), ((), ())),
        preferred_element_type=jnp.float32)             # [bb, bo]

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("n", "block_b", "block_o", "block_w", "interpret")
)
def tl2_matmul(
    x: jax.Array,
    words: jax.Array,
    n: int,
    *,
    block_b: int = 8,
    block_o: int = 128,
    block_w: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas TL2 matmul: ``y[b, o] = Σ_k x[b, k] · trits(words)[o, k]``.

    Args:
      x:     [B, N'] activations (f32/bf16/int8); padded internally to the
             full unpacked width ``words.shape[1] * 10``.
      words: [O, W] uint16 TL2-packed ternary weights (:func:`pack_tl2`).
      n:     logical K (columns beyond n are zero by construction).
      block_*: VMEM tile sizes; ``block_w`` counts packed words (×10 x cols).
      interpret: interpret mode; ``None`` compiles on TPU and interprets
             elsewhere.

    Returns [B, O] float32.
    """
    B = x.shape[0]
    O, W = words.shape
    full = W * TRITS_PER_WORD
    if x.shape[1] < full:
        x = jnp.pad(x, ((0, 0), (0, full - x.shape[1])))
    x = x.astype(jnp.float32)

    block_b = min(block_b, B)
    block_o = min(block_o, O)
    block_w = min(block_w, W)
    pad_b = (-B) % block_b
    pad_o = (-O) % block_o
    pad_w = (-W) % block_w
    if pad_b or pad_w:
        x = jnp.pad(x, ((0, pad_b), (0, pad_w * TRITS_PER_WORD)))
    if pad_o or pad_w:
        # pad word 0 decodes to digit-0 pairs = (-1, -1) trits, but the
        # matching x columns are zero-padded so the products vanish; padded
        # output rows are sliced off below.
        words = jnp.pad(words, ((0, pad_o), (0, pad_w)))
    Bp, Op, Wp = B + pad_b, O + pad_o, W + pad_w

    out = pl.pallas_call(
        _tl2_kernel,
        grid=(Bp // block_b, Op // block_o, Wp // block_w),
        in_specs=[
            pl.BlockSpec((block_b, block_w * TRITS_PER_WORD),
                         lambda i, j, k: (i, k)),
            pl.BlockSpec((block_o, block_w), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Op), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, words)
    return out[:B, :O]
