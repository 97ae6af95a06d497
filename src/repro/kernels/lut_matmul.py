"""Pallas TPU kernel for the two-phase LUT ternary matmul (paper Fig. 2/3).

TPU mapping of the paper's architecture (see DESIGN.md §3):

* **Build phase** — for each group of ``mu`` activations, the symmetry-reduced
  partial-sum table is a tiny dense contraction ``x_groups @ C.T`` with the
  ternary combo matrix ``C`` [T+1, mu].  On TPU this runs on the MXU; the
  hardware's optimized adder tree *is* this contraction (C's zeros = sparsity
  pruning, its ±1 structure = conditional add).
* **Fetch & accumulate phase** — two selectable lowerings:
  - ``fetch="onehot"``: signed one-hot of the weight keys contracted against
    the tables (MXU-resident; the symmetry sign-flip is folded into the
    one-hot values — a "free" inversion exactly like the FAC unit's).
  - ``fetch="gather"``: ``take_along_axis`` per group (VPU dynamic gather,
    closest to the literal read-out MUX).

Tiling: grid = (B/bb, O/bo, G/bg); the reduction over group-tiles is the
innermost grid dim with a VMEM accumulator in the output ref, mirroring the
output-stationary Output Buffer of Fig. 3.  ``L`` (parallel LUTs) maps to the
``bg`` groups resident in VMEM; ``K`` (parallel fetchers) maps to ``bo``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import encoding
from repro.kernels.backend import resolve_interpret


def _lut_kernel(x_ref, keys_ref, out_ref, *, mu: int, fetch: str):
    """One (bb, bo) output tile, one bg-group reduction step.

    x_ref:    [bb, bg*mu]   activation slice (float)
    keys_ref: [bo, bg]      encoded ternary weight keys (uint8/uint16)
    out_ref:  [bb, bo]      accumulator (float32)
    """
    k = pl.program_id(2)
    bb, bgmu = x_ref.shape
    bg = bgmu // mu
    T = encoding.table_size(mu)
    ib = encoding.idx_bits(mu)

    # ---- Build phase: tables[b, g, t] = dot(C[t], x[b, g*mu:(g+1)*mu]) ----
    # The combo matrix is synthesized in-kernel from iota arithmetic (Pallas
    # kernels cannot capture array constants): row t holds the base-3 digits
    # of v = center+1+t, minus 1; the reserved row T is the all-zero combo.
    ti = jax.lax.broadcasted_iota(jnp.int32, (T + 1, mu), 0)
    di = jax.lax.broadcasted_iota(jnp.int32, (T + 1, mu), 1)
    v = jnp.where(ti == T, T, T + 1 + ti)  # center == T
    C = (v // (3**di)) % 3 - 1  # [T+1, mu] in {-1,0,1}
    xg = x_ref[...].reshape(bb, bg, mu)
    tables = jax.lax.dot_general(
        xg, C.astype(xg.dtype),
        dimension_numbers=(((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bb, bg, T+1]

    # ---- Fetch & accumulate phase ----
    keys = keys_ref[...].astype(jnp.int32)  # [bo, bg]
    sym = keys >> ib
    idx = keys & ((1 << ib) - 1)
    sign = jnp.where(sym == 1, -1.0, 1.0).astype(jnp.float32)  # [bo, bg]

    if fetch == "onehot":
        # Signed one-hot: [bo, bg, T+1]; sign folded in (free inversion).
        iota = jax.lax.broadcasted_iota(jnp.int32, (*idx.shape, T + 1), 2)
        oh = jnp.where(iota == idx[..., None], sign[..., None], 0.0)
        partial = jax.lax.dot_general(
            tables.astype(jnp.float32), oh,
            dimension_numbers=(((1, 2), (1, 2)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bb, bo]
    elif fetch == "gather":
        # Literal read-out MUX: gather entry idx[o, g] from tables[b, g, :].
        idx_b = jnp.broadcast_to(idx.T[None], (bb, bg, idx.shape[0]))  # [bb,bg,bo]
        fetched = jnp.take_along_axis(tables.astype(jnp.float32), idx_b, axis=2)
        partial = jnp.sum(fetched * sign.T[None], axis=1)  # [bb, bo]
    else:  # pragma: no cover - guarded by ops wrapper
        raise ValueError(fetch)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit,
    static_argnames=("mu", "block_b", "block_o", "block_g", "fetch", "interpret"),
)
def lut_matmul(
    x: jax.Array,
    keys: jax.Array,
    mu: int,
    *,
    block_b: int = 8,
    block_o: int = 128,
    block_g: int = 128,
    fetch: str = "onehot",
    interpret: bool | None = None,
) -> jax.Array:
    """Two-phase LUT matmul: ``y[b, o] = Σ_n x[b, n] · decode(keys)[o, n]``.

    Args:
      x:    [B, N] activations (f32/bf16); N must equal keys.shape[1] * mu.
      keys: [O, G] encoded weight keys (``encoding.encode_weight_matrix``).
      mu:   LUT group size.
      block_*: VMEM tile sizes (the generator's KernelPlan supplies aligned
        values for real TPU; tests shrink them).
      interpret: run the kernel body in interpret mode; ``None`` compiles
        on TPU and interprets elsewhere.

    Returns:
      [B, O] float32.
    """
    B, N = x.shape
    O, G = keys.shape
    if N != G * mu:
        raise ValueError(f"N={N} != G*mu={G * mu}")

    block_b = min(block_b, B)
    block_o = min(block_o, O)
    block_g = min(block_g, G)
    pad_b = (-B) % block_b
    pad_o = (-O) % block_o
    pad_g = (-G) % block_g
    if pad_b or pad_g:
        x = jnp.pad(x, ((0, pad_b), (0, pad_g * mu)))
    if pad_o or pad_g:
        # padded groups encode all-zero (key 'T' with sym=0 fetches the
        # hardwired zero entry)
        zero_key = jnp.full((1,), encoding.table_size(mu), dtype=keys.dtype)
        keys = jnp.pad(keys, ((0, pad_o), (0, pad_g)), constant_values=zero_key[0])
    Bp, Op, Gp = B + pad_b, O + pad_o, G + pad_g

    grid = (Bp // block_b, Op // block_o, Gp // block_g)
    out = pl.pallas_call(
        functools.partial(_lut_kernel, mu=mu, fetch=fetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_g * mu), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_o, block_g), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Op), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, keys)
    return out[:B, :O]
