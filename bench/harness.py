"""One benchmark run: set up the served model, warm it, measure a window,
check the outputs against the reference, and build the result line.

The program is used through a narrow surface only:
``repro.launch.serve.load_served``, ``DecodeEngine``'s constructor,
``ContinuousScheduler`` (see ``bench/window.py``),
``DecodeEngine.kernel_choices``, ``SchedulerStats`` and
``repro.launch.compile_cache.enable_compile_cache``.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

from bench import arch, correct, traffic, window
from bench import trace as trace_mod

ROOT = Path(__file__).resolve().parents[1]
#: seconds of the window the traced run profiles, ending at its close
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


@dataclass
class Cell:
    spec: dict          # the workload entry of BENCHMARK.json
    bench: dict         # BENCHMARK.json
    model: dict         # the configuration file
    mix: dict           # the traffic file
    limits: dict        # bench/limits/<cell>.json, or {} before it exists


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    specs = {w["name"]: w for w in bench["workloads"]}
    if workload not in specs:
        raise KeyError(f"no workload {workload!r}; known: {sorted(specs)}")
    spec = specs[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg_file = cfgs[spec["config"]]["file"]
    model = json.loads((root / cfg_file).read_text())
    arch.load(model, cfg_file)
    mix = traffic.load(root / "bench" / "traffic" / f"{spec['traffic']}.json")
    lim = root / "bench" / "limits" / f"{workload}.json"
    limits = json.loads(lim.read_text()) if lim.exists() else {}
    return Cell(spec, bench, model, mix, limits)


def check_devices(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']}")
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {info['platform']} device(s)")
    return info


def program_config(model: dict):
    """The program's configuration for a configuration file, checked key by
    key against the file: every key of its architecture module's
    ``PROGRAM_KEYS``."""
    from repro.configs.registry import get_config

    srv = model["serving"]
    cfg = get_config(srv["arch"]).with_(act_dtype=srv["act_dtype"])
    want = {attr: model[key]
            for key, attr in arch.load(model).PROGRAM_KEYS.items()}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config differs from {model['name']}: "
                         f"{bad}")
    return cfg


def build(cfg, seed: int, mix: dict, served=None):
    """The served model from the seed (or ``served``, already made), on the
    device, with every shape the traffic uses compiled: one admission of two
    chunks, then decode."""
    import jax
    from repro.launch.serve import load_served
    from repro.serving.engine import DecodeEngine, Request
    from repro.serving.scheduler import ContinuousScheduler

    if served is None:
        served = load_served(cfg, seed=seed)
    jax.block_until_ready(served)
    engine = DecodeEngine(served, cfg, batch_size=mix["slots"],
                          max_len=mix["max_len"])
    sched = ContinuousScheduler(engine)
    sched.submit(Request(prompt=[2] * (engine.prefill_chunk + 1),
                         max_new_tokens=2))
    sched.run(max_steps=64)
    return engine


class Profiler:
    """Starts and stops the JAX profiler from the window's hooks."""

    def __init__(self, directory: Path, clock):
        self.dir = directory
        self.clock = clock
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        jax.profiler.start_trace(str(self.dir))
        self.t_start = self.clock()

    def stop(self):
        import jax

        self.t_stop = self.clock()
        jax.profiler.stop_trace()


def peak_memory(count: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_tpu: bool = True,
             tmp: Path | None = None, compile_cache: bool = True) -> dict:
    """Everything one invocation of ``bench/run.py`` does; returns the
    result line's object.  The tests run it on the CPU with
    ``require_tpu=False`` and without the persistent compile cache."""
    import tempfile

    cell = load_cell(workload, root)
    dev = check_devices(cell.spec["chips"], require_tpu)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    if compile_cache:
        say("compile cache", enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = window.CompileCounter()
    cfg = program_config(cell.model)
    engine = build(cfg, seed, cell.mix)
    kernels = engine.kernel_choices()
    say("kernels", sorted(set(kernels.values())))
    say(f"set-up before traffic {time.perf_counter() - t_start:.3f} s, "
        f"{compiles.count} compiles")

    clock = time.perf_counter
    with tempfile.TemporaryDirectory(dir=tmp) as tdir:
        prof = Profiler(Path(tdir), clock) if trace else None
        hooks = []
        if prof is not None:
            t_close = cell.mix["warmup_s"] + seconds
            hooks = [(t_close - min(TRACE_SECONDS, seconds / 2), prof.start),
                     (t_close, prof.stop)]
        win = window.drive(engine, cell.mix, seed, cfg.vocab_size,
                           seconds=seconds, compiles=compiles, at=hooks,
                           block=trace, annotate=trace, clock=clock)
        setup_s = win.t0 + win.open - t_start
        mem = peak_memory(cell.spec["chips"])
        if win.compiles_in_window:
            say(f"FAULT: {win.compiles_in_window} compile(s) inside the "
                f"measured window")
        summary = None
        if prof is not None:
            summary = trace_mod.reduce(Path(tdir), prof.t_start - win.t0,
                                       prof.t_stop - win.t0)
    prefill_chunk = engine.prefill_chunk
    del engine
    gc.collect()

    checks = correct.verify(win, cell.model, seed, cell.limits)
    out = {"correct": correct.passed(checks),
           "attempted": len(win.attempted()), "failed": 0}
    if trace:
        ctx = Context(cell=cell, win=win, kernels=kernels, trace=summary,
                      device_kind=dev["kind"], prefill_chunk=prefill_chunk)
        out["metrics"] = per_layer(ctx)
    else:
        out["metrics"] = end_to_end(cell, win, setup_s)
    out["device"] = dict(dev, memory_peak_bytes=mem)
    if trace:
        out["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_gaps}
    late = win.lateness
    say(f"window {win.seconds:.1f} s: {len(win.due_in_window())} due, "
        f"{len(win.completed_in_window())} completed, "
        f"{win.served_tokens()} served tokens of those due, "
        f"{win.tokens_in_window} tokens visible in all, "
        f"{win.decode_steps_in_window} decode steps, "
        f"queued {win.queued_at_open} -> {win.queued_at_close}, "
        f"generator late max {max(late) if late else 0.0:.4f} s")
    for name, c in checks.items():
        say(f"check {name} = {c['value']} limit {c['limit']}")
    out["checks"] = checks
    return out


def end_to_end(cell: Cell, win: window.Window, setup_s: float) -> dict:
    """The cell's end-to-end metrics, each taken over the whole window."""
    from bench.stats import percentile

    values = {"served_tok_s": (win.served_tok_s(), "tokens/s"),
              "setup_s": (setup_s, "s")}
    if win.due_in_window():
        values["ttft_p90_s"] = (percentile(win.ttfts(), 90), "s")
    if win.tpots():
        values["tpot_p90_s"] = (percentile(win.tpots(), 90), "s")
    out = {}
    for m in cell.bench["end_to_end"]:
        if "workloads" in m and cell.spec["name"] not in m["workloads"]:
            continue
        if m["name"] in values:
            v, unit = values[m["name"]]
            out[m["name"]] = {"value": v, "unit": unit}
    return out


@dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Cell
    win: window.Window
    kernels: dict
    trace: object
    device_kind: str
    prefill_chunk: int


def per_layer(ctx: Context) -> dict:
    """Each per-layer metric of this cell, from its reader
    ``bench/metrics/<name before the first dot>.py``; a reader that finds
    nothing returns None and the metric is left out."""
    import importlib.util

    out = {}
    for m in ctx.cell.bench["per_layer"]:
        if "workloads" in m and ctx.cell.spec["name"] not in m["workloads"]:
            continue
        base = m["name"].split(".")[0]
        path = ROOT / "bench" / "metrics" / f"{base}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{base}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
