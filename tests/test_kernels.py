"""Pallas kernels vs pure-jnp oracles (interpret mode), sweeping shapes,
dtypes, group sizes and block geometries."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import encoding
from repro.kernels import ref
from repro.kernels.dequant_matmul import packed_matmul
from repro.kernels.lut_matmul import lut_matmul
from repro.kernels.signflip_matmul import signflip_matmul


def _data(seed, B, O, N, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, N)), dtype)
    w = jnp.asarray(rng.integers(-1, 2, size=(O, N)), jnp.int8)
    return x, w


@pytest.mark.parametrize("B,O,N,bb,bo,bn", [
    (1, 8, 16, 1, 8, 16),
    (4, 37, 60, 2, 16, 20),
    (8, 128, 256, 8, 64, 64),
    (3, 5, 7, 2, 4, 5),
])
def test_signflip_kernel(B, O, N, bb, bo, bn):
    x, w = _data(0, B, O, N)
    y = signflip_matmul(x, w, block_b=bb, block_o=bo, block_n=bn)
    y_ref = ref.signflip_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_signflip_dtypes(dtype):
    x, w = _data(1, 4, 16, 40, dtype)
    y = signflip_matmul(x, w, block_b=2, block_o=8, block_n=20)
    y_ref = x.astype(jnp.float32) @ w.astype(jnp.float32).T
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-1 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("B,O,N", [(1, 8, 15), (4, 37, 60), (2, 9, 101)])
def test_packed_kernel(B, O, N):
    x, w = _data(2, B, O, N)
    p = encoding.pack_base3(w)
    y = packed_matmul(x, p, N, block_b=2, block_o=8, block_n=20)
    y_ref = ref.packed_matmul_ref(x, p, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-4)


def test_packed_kernel_prefill_chunk_one_row_block():
    """A 32-row prefill chunk at the default (single) row block."""
    x, w = _data(4, 32, 136, 700)
    p = encoding.pack_base3(w)
    y = packed_matmul(x, p, 700)
    y_ref = ref.packed_matmul_ref(x, p, 700)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("mu", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("fetch", ["onehot", "gather"])
def test_lut_kernel_mu_sweep(mu, fetch):
    B, O, N = 4, 21, 36
    x, w = _data(3, B, O, N)
    keys = encoding.encode_weight_matrix(w, mu)
    G = keys.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, G * mu - N)))
    y = lut_matmul(xp, keys, mu, block_b=2, block_o=8, block_g=5, fetch=fetch)
    y_ref = ref.lut_matmul_ref(xp, keys, mu)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(1, 16), st.integers(1, 48),
       st.integers(1, 6), st.integers(0, 2**31 - 1))
def test_lut_kernel_property(mu, O, N, B, seed):
    x, w = _data(seed, B, O, N)
    keys = encoding.encode_weight_matrix(w, mu)
    G = keys.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, G * mu - N)))
    y = lut_matmul(xp, keys, mu, block_b=4, block_o=16, block_g=8)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ w.astype(jnp.float32).T), rtol=1e-4, atol=1e-3)


def test_ops_wrappers_roundtrip():
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 40)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 40)), jnp.float32)
    from repro.core.quantization import dequantize, ternarize
    w_t, s = ternarize(w)
    y_want = np.asarray(x @ dequantize(w_t, s, jnp.float32).T)

    keys, scale = ops.encode_for_lut(w, 3)
    G = keys.shape[1]
    y1 = ops.ternary_linear_lut(jnp.pad(x, ((0, 0), (0, G * 3 - 40))), keys, scale, 3)
    np.testing.assert_allclose(np.asarray(y1), y_want, rtol=2e-2, atol=1e-2)

    packed, scale = ops.encode_packed(w)
    y2 = ops.ternary_linear_packed(x, packed, scale, 40)
    np.testing.assert_allclose(np.asarray(y2), y_want, rtol=2e-2, atol=1e-2)

    y3 = ops.ternary_linear_signflip(x, w_t, s)
    np.testing.assert_allclose(np.asarray(y3), y_want, rtol=2e-2, atol=1e-2)
