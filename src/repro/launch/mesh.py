"""Production mesh construction.

Target hardware: TPU v5e pods — 256 chips/pod (16×16), 2 pods = 512 chips for
the multi-pod dry-run.  Defined as functions (never module-level constants)
so importing this module never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: JAX ≥0.9 defaults to Explicit axes,
    under which the serving path's gathers (embedding, base-3 decode table)
    raise ``ShardingTypeError``; GSPMD propagation is what this repo uses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1):
    """Small meshes for tests/examples on real local devices."""
    assert devices % model_parallel == 0
    return _make_mesh((devices // model_parallel, model_parallel),
                      ("data", "model"))


def make_serving_mesh(spec: str):
    """Parse a ``--mesh`` CLI spec into a serving mesh over local devices.

    ``"DxM"`` → ``(data, model)``; ``"PxDxM"`` → ``(pod, data, model)``.
    E.g. ``--mesh 1x8`` is 8-way tensor parallelism, ``--mesh 2x4`` shards
    MoE experts 2-way on data with 4-way TP inside each expert.  The axis
    product must match the available device count (on CPU CI, forced via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    try:
        shape = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}; expected e.g. '1x8'")
    if len(shape) == 2:
        axes = ("data", "model")
    elif len(shape) == 3:
        axes = ("pod", "data", "model")
    else:
        raise ValueError(
            f"bad mesh spec {spec!r}; expected 'DxM' or 'PxDxM'")
    n = 1
    for s in shape:
        n *= s
    avail = len(jax.devices())
    if n != avail:
        raise ValueError(
            f"mesh {spec!r} needs {n} devices but {avail} are available "
            "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return _make_mesh(shape, axes)


# Hardware constants for the roofline (assignment block).
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
CHIPS_SINGLE_POD = 256
CHIPS_MULTI_POD = 512
