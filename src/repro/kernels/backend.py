"""Backend resolution shared by every Pallas kernel entry point."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` (every kernel's default) compiles with Mosaic on TPU and
    runs the Pallas interpreter on every other backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
