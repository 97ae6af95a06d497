"""Populate the ternary-matmul autotune cache across the config registry.

For every architecture in ``repro.configs.registry`` this sweep enumerates
the per-layer ternary matmul shapes a serving step issues
(:func:`repro.models.decode.layer_matmul_shapes`) and benchmarks every
registered kernel on each, persisting the measurements to the dispatch
cache (``$REPRO_AUTOTUNE_CACHE``, default ``~/.cache/repro/autotune.json``).
After a sweep, ``ternary_matmul(policy="auto")`` dispatches every serving
projection on measured wall-times instead of the analytical prior.

Usage::

    PYTHONPATH=src python benchmarks/autotune_sweep.py                  # smoke dims
    PYTHONPATH=src python benchmarks/autotune_sweep.py --full           # real dims
    PYTHONPATH=src python benchmarks/autotune_sweep.py --archs qwen3-0.6b \
        --batch-sizes 1 8 --reps 5

Real-dimension sweeps on CPU run the Pallas kernels in interpret mode and
can take a long time; the default therefore sweeps the structure-preserving
smoke-scale configs (``--full`` opts into real dims, intended for TPU).
"""

from __future__ import annotations

import argparse

from repro.configs.registry import ARCHS, get_smoke_config
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.decode import layer_grouped_matmul_shapes, layer_matmul_shapes


def sweep(archs: list[str], batch_sizes: list[int], *, full: bool = False,
          dtypes: tuple[str, ...] | None = None, reps: int = 3,
          verbose: bool = True) -> dict:
    """``dtypes=None`` benchmarks each arch at its own serving activation
    dtype (``cfg.dtype``, normally bfloat16) — the dtype the cache key must
    match for serving dispatch to hit the entries.  Group size is always the
    arch's ``cfg.mu`` for the same reason.  MoE archs contribute their
    grouped expert-stack problems ``(E, C, K, N)`` alongside the dense
    triples (job key: ``e=None`` marks a dense problem)."""
    cache = dispatch.get_autotune_cache()
    jobs: set[tuple[int | None, int, int, int, str, int]] = set()
    for arch in archs:
        cfg = ARCHS[arch] if full else get_smoke_config(arch)
        for b in batch_sizes:
            for dt in (dtypes or (cfg.dtype,)):
                for (m, k, n) in layer_matmul_shapes(cfg, b):
                    jobs.add((None, m, k, n, dt, cfg.mu))
                for (e, c, k, n) in layer_grouped_matmul_shapes(cfg, b):
                    jobs.add((e, c, k, n, dt, cfg.mu))

    results = {}
    key = lambda j: tuple(x if x is not None else -1 for x in j)
    for i, (e, m, k, n, dt, mu) in enumerate(sorted(jobs, key=key)):
        timings = dispatch.autotune(m, k, n, dt, reps=reps, cache=cache,
                                    save=False, mu=mu, e=e)
        results[(e, m, k, n, dt, mu)] = timings
        if verbose and timings:
            best = min(timings, key=timings.get)
            tag = f"E{e} " if e is not None else ""
            print(f"[{i + 1}/{len(jobs)}] {tag}M{m} K{k} N{n} mu{mu} {dt}: "
                  f"best={best} ({timings[best]:.0f}us of "
                  f"{len(timings)} kernels)")
    cache.save()
    if verbose:
        print(f"cache: {len(cache)} entries -> {cache.path}")
    return results


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--archs", nargs="*", default=sorted(ARCHS),
                    choices=sorted(ARCHS))
    ap.add_argument("--batch-sizes", nargs="*", type=int, default=[1, 8])
    ap.add_argument("--dtypes", nargs="*", default=None,
                    choices=["float32", "bfloat16", "float16", "int8"],
                    help="override per-arch serving dtype (default: each "
                         "arch's cfg.dtype)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--full", action="store_true",
                    help="sweep real model dims (slow on CPU) instead of "
                         "smoke-scale configs")
    args = ap.parse_args(argv)
    enable_compile_cache()
    sweep(args.archs, args.batch_sizes, full=args.full,
          dtypes=tuple(args.dtypes) if args.dtypes else None, reps=args.reps)


if __name__ == "__main__":
    main()
