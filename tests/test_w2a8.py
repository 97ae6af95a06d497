"""W1.58A8 kernel (ternary weights × INT8 activations, int32 accumulation):
the paper's Table-I BitNet b1.58 operating point."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import encoding
from repro.core.quantization import quantize_activations_int8, ternarize
from repro.kernels.dequant_matmul import ROW_BLOCK_CAP, row_blocks
from repro.kernels.w2a8_matmul import w2a8_linear, w2a8_matmul


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(1, 24), st.integers(1, 50),
       st.integers(0, 2**31 - 1))
def test_w2a8_exact_int32(B, O, N, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, size=(B, N)), jnp.int8)
    w = jnp.asarray(rng.integers(-1, 2, size=(O, N)), jnp.int8)
    y = w2a8_matmul(x, encoding.pack_base3(w), N, block_b=2, block_o=8, block_n=20)
    ref = np.asarray(x, np.int64) @ np.asarray(w, np.int64).T
    np.testing.assert_array_equal(np.asarray(y, np.int64), ref)  # bit exact


#: rows of a single-token call, a decode step over the slots, a prefill
#: chunk, one row past it, and one row past the cap (two row blocks)
ROWS = [1, 8, 32, 33, ROW_BLOCK_CAP + 1]


@pytest.mark.parametrize("M", ROWS)
def test_w2a8_one_row_block_exact(M):
    """The default row block is bit-exact against int64 and equal, bit for
    bit, to the 8-row grid.  O and N each span two padded default tiles."""
    O, N = 136, 700
    rng = np.random.default_rng(M)
    x = jnp.asarray(rng.integers(-127, 128, size=(M, N)), jnp.int8)
    w = jnp.asarray(rng.integers(-1, 2, size=(O, N)), jnp.int8)
    p = encoding.pack_base3(w)
    y = np.asarray(w2a8_matmul(x, p, N))
    ref = np.asarray(x, np.int64) @ np.asarray(w, np.int64).T
    np.testing.assert_array_equal(y.astype(np.int64), ref)
    np.testing.assert_array_equal(y, np.asarray(w2a8_matmul(x, p, N,
                                                            block_b=8)))


def _row_grid(M, **kw):
    """The row dimension of the grid ``w2a8_matmul`` launches for M rows."""
    x = jax.ShapeDtypeStruct((M, 50), jnp.int8)
    p = jax.ShapeDtypeStruct((24, 10), jnp.uint8)
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(getattr(inner, "jaxpr", inner))

    walk(jax.make_jaxpr(lambda a, b: w2a8_matmul(a, b, 50, **kw))(x, p).jaxpr)
    (grid,) = grids
    return grid[0]


@pytest.mark.parametrize("M", [1, 8, 32, 33, ROW_BLOCK_CAP,
                               ROW_BLOCK_CAP + 1, 3 * ROW_BLOCK_CAP + 5])
def test_row_blocks_one_block_up_to_cap(M):
    """One row block for M <= cap, ceil(M / cap) above it; the kernel's grid
    is the helper's."""
    block, count = row_blocks(M)
    if M <= ROW_BLOCK_CAP:
        assert (block, count) == (M, 1)
    else:
        assert (block, count) == (ROW_BLOCK_CAP, -(-M // ROW_BLOCK_CAP))
    assert _row_grid(M) == count
    # an explicit block_b still tiles at that size
    assert row_blocks(M, 8) == (min(M, 8), -(-M // min(M, 8)))
    assert _row_grid(M, block_b=8) == row_blocks(M, 8)[1]


def test_w2a8_linear_rescale():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 40)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 40)), jnp.float32)
    w_t, w_scale = ternarize(w)
    y = w2a8_linear(x, encoding.pack_base3(w_t), w_scale, 40)
    # reference: fake-quant both sides in fp
    x_q, x_scale = quantize_activations_int8(x)
    ref = (np.asarray(x_q, np.float32) * np.asarray(x_scale)) @ \
        (np.asarray(w_t, np.float32) * float(w_scale)).T
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


def test_w2a8_activation_bytes_halved():
    """The W2A8 path streams half the activation bytes of bf16."""
    x = jnp.zeros((8, 1024), jnp.bfloat16)
    x_q, _ = quantize_activations_int8(x.astype(jnp.float32))
    assert x_q.dtype == jnp.int8 and x_q.nbytes * 2 == x.nbytes
