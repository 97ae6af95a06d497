"""Public jit'd entry points for the ternary kernels.

``ternary_linear_*`` apply the BitNet scale handling around the raw kernels
(the kernels work on unscaled trits; the absmean weight scale and optional
INT8 activation scale are rank-1 corrections applied outside the hot loop).

``impl`` selection:
  * ``"lut"``      — two-phase LUT kernel (paper's architecture),
  * ``"signflip"`` — binary-plane MXU baseline (Fig. 1 middle),
  * ``"dequant"``  — packed 1.6-bit streaming dequant (deployment path),
all validated against ``ref.py`` in ``tests/test_kernels.py``.  Only the
packed path compiles for TPU; the LUT and sign-flip kernels run in the
interpreter (see ``dispatch.KernelSpec.tpu``).

``interpret=None`` (the default) compiles the kernels with Mosaic on TPU and
runs them in the Pallas interpreter on every other backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import encoding
from repro.kernels.dequant_matmul import packed_matmul
from repro.kernels.lut_matmul import lut_matmul
from repro.kernels.signflip_matmul import signflip_matmul


def _flatten_batch(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def ternary_linear_lut(x, keys, scale, mu: int, *,
                       interpret: bool | None = None, fetch: str = "onehot",
                       block_o: int = 128, block_g: int = 128):
    """y = (x @ decode(keys).T) * scale via the LUT kernel.  x: [..., N]."""
    x2, lead = _flatten_batch(x)
    y = lut_matmul(x2.astype(jnp.float32), keys, mu, fetch=fetch,
                   block_o=block_o, block_g=block_g, interpret=interpret)
    y = y * jnp.asarray(scale, jnp.float32)
    return y.reshape(*lead, -1).astype(x.dtype)


def ternary_linear_signflip(x, w_t, scale, *, interpret: bool | None = None):
    x2, lead = _flatten_batch(x)
    y = signflip_matmul(x2.astype(jnp.float32), w_t, interpret=interpret)
    y = y * jnp.asarray(scale, jnp.float32)
    return y.reshape(*lead, -1).astype(x.dtype)


def ternary_linear_packed(x, packed, scale, n: int, *,
                          interpret: bool | None = None):
    x2, lead = _flatten_batch(x)
    y = packed_matmul(x2.astype(jnp.float32), packed, n, interpret=interpret)
    y = y * jnp.asarray(scale, jnp.float32)
    return y.reshape(*lead, -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("mu",))
def encode_for_lut(w: jax.Array, mu: int):
    """Offline step: master weights → (keys, scale) for the LUT kernel."""
    from repro.core.quantization import ternarize

    w_t, scale = ternarize(w)
    keys = encoding.encode_weight_matrix(w_t, mu)
    return keys, scale


@jax.jit
def encode_packed(w: jax.Array):
    """Offline step: master weights → (packed, scale) deployment artifact."""
    from repro.core.quantization import ternarize

    w_t, scale = ternarize(w)
    return encoding.pack_base3(w_t), scale
