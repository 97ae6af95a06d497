"""Architecture modules: everything of the benchmark that knows a
configuration's layer equations.

A configuration file names its module under ``"architecture"``; the module
``bench/arch/<name>.py`` gives four names:

- ``Reference(model, seed)``, the plain float32 reference that decides
  ``correct``, with ``score(tokens, rows, targets, act_bits)`` returning the
  gap of each target's logit below the best one, and the best token;
- ``PROGRAM_KEYS``, ``{file key: ModelConfig attribute}``: the keys that
  ``harness.program_config`` holds the program's configuration to;
- ``least_seconds(m, model, act_bytes, peak, hbm)``, the least time of the
  ternary projections of one forward over ``m`` rows (``ternary_roofline``);
- ``token_flops(model, context, head)``, the model FLOPs of one token
  (``mfu``).

A module imports nothing of the program under test.
"""

from __future__ import annotations

import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent


def names() -> list[str]:
    """The architecture modules that exist."""
    return sorted(p.stem for p in HERE.glob("*.py") if p.stem != "__init__")


def load(model: dict, file=None):
    """The architecture module the configuration names; a missing or
    unknown name is an error that names the configuration's file."""
    name = model.get("architecture")
    where = file or f"the configuration {model.get('name')!r}"
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"{where} names no architecture module (key "
                         f"'architecture': {name!r}); known: {names()}")
    full = f"{__name__}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"{where} names the architecture {name!r}, which "
                         f"has no module; known: {names()}") from None
