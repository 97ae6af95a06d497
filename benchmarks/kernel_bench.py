"""Kernel microbenchmarks: wall time of every registered ternary matmul path,
dense AND grouped (batched-expert), with a committed JSON trajectory.

Kernels are enumerated and executed through the unified dispatch layer
(``repro.kernels.dispatch``) so this benchmark measures exactly what
``ternary_matmul(policy="fixed:<name>")`` / ``grouped_ternary_matmul`` run,
and the timings are written into the autotune cache — running the benchmark
*is* autotuning for its shapes.  CPU interpret-mode numbers for the Pallas
kernels are *functional* timings (the TPU target numbers come from the
roofline analysis); the ``ref``/``grouped_ref`` XLA paths are what the
serving stack executes on CPU and their timings are real.

The grouped section benches the phi3.5-moe expert-stack operating points
(decode: per-expert capacity from a B=4 batch; prefill: capacity of one
admission chunk) against the **eager full-dequant einsum baseline** — the
pre-dispatch MoE path that unpacked ``[E, d_out, d_in]`` dense weights every
forward.  ``speedup_vs_einsum`` is the trajectory headline: it must stay
> 1 at the decode point (CI smoke asserts this).

Writes ``BENCH_kernels.json``::

  {"schema_version": 1, "backend": ..., "smoke": true, "arch": ...,
   "dense": {"shape": {"M","K","N"}, "kernels": {name: us}, "best": name},
   "dense_int8": same shape, W1.58A8 (pre-quantized int8 activations),
   "grouped": [{"op_point": "decode"|"prefill"|"decode_a8",
                "shape": {"E","C","K","N"}, "kernels": {name: us},
                "best": name, "best_us": us, "einsum_baseline_us": us,
                "speedup_vs_einsum": ratio}, ...],
   "a8_bytes": static bytes-moved at the decode point (bf16 dense vs the
               grouped_w2a8 / grouped_tl2 packed streams + bits/weight) —
               the non-flaky bandwidth gate CI asserts on}

Run:  PYTHONPATH=src python benchmarks/kernel_bench.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache

#: serving batch / admission chunk defining the two MoE operating points
MOE_ARCH = "phi3.5-moe-42b-a6.6b"
DECODE_BATCH = 4
PREFILL_CHUNK = 16


def _time_fn(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / reps * 1e6


def _einsum_baseline_us(e: int, c: int, k: int, n: int, dtype: str,
                        reps: int = 3, seed: int = 0) -> float:
    """The pre-dispatch MoE path: eagerly unpack the WHOLE expert stack to a
    dense ``[E, N, K]`` tensor inside the jitted step, then one einsum."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(e, c, k)), dtype)
    packed = encoding.pack_base3(
        jnp.asarray(rng.integers(-1, 2, size=(e, n, k)), jnp.int8))
    scale = jnp.ones((e,), jnp.float32)

    @jax.jit
    def eager(t, pk):
        w_t = encoding.unpack_base3(pk, k)          # [E, N, K] every call
        y = jnp.einsum("ecd,efd->ecf", t, w_t.astype(t.dtype))
        return y * scale[:, None, None].astype(y.dtype)

    return _time_fn(eager, x, packed, reps=reps)


def bench_dense(cache, *, m: int = 8, n_out: int = 512, k_in: int = 1024,
                reps: int = 3, act: str = "float32") -> dict:
    timings = dispatch.autotune(m, k_in, n_out, act, reps=reps,
                                cache=cache, save=False)
    return {"shape": {"M": m, "K": k_in, "N": n_out},
            "kernels": {name: round(us, 2) for name, us in timings.items()},
            "best": min(timings, key=timings.get)}


def a8_bytes_moved(*, e: int, c: int, k: int, n: int, mu: int = 3) -> dict:
    """Static bytes-moved comparison at a grouped decode operating point:
    the W1.58A8 packed paths versus streaming a dense bf16 expert stack.
    Decode is bandwidth-bound (every expert's weights stream every step), so
    bytes moved per step is the property CI gates — unlike wall-clock on a
    shared runner, it cannot flake."""
    per = {
        "bf16_dense": 2 * k * n,
        "grouped_w2a8": int(dispatch.get_kernel("grouped_w2a8")
                            .weight_bytes(k, n, mu)),
        # the TL2 packed artifact (5 base-9 digit pairs per uint16 =
        # 1.6 b/w) — taken from the Pallas spec: the grouped_tl2 XLA ref
        # deliberately charges its onehot decode in the cost model, which
        # is an interpret-mode dispatch-ordering device, not HBM traffic
        "tl2_packed": int(dispatch.get_kernel("tl2").weight_bytes(k, n, mu)),
    }
    return {
        "shape": {"E": e, "C": c, "K": k, "N": n},
        "bytes_per_expert_step": per,
        "bytes_per_step": {nm: b * e for nm, b in per.items()},
        "bits_per_weight": {nm: round(8 * b / (k * n), 3)
                            for nm, b in per.items()},
    }


def bench_grouped(cache, *, smoke: bool, reps: int = 3) -> list[dict]:
    from repro.configs.registry import get_config, get_smoke_config
    from repro.models.decode import layer_grouped_matmul_shapes

    cfg = get_smoke_config(MOE_ARCH) if smoke else get_config(MOE_ARCH)
    decode_shapes = layer_grouped_matmul_shapes(cfg, DECODE_BATCH)
    points = [("decode", cfg.dtype, decode_shapes),
              ("prefill", cfg.dtype,
               layer_grouped_matmul_shapes(cfg, 1, seq_len=PREFILL_CHUNK)),
              # the W1.58A8 decode path: per-expert int8 activations through
              # the same expert stacks (routes grouped_w2a8/grouped_tl2)
              ("decode_a8", "int8", decode_shapes)]
    out = []
    for op_point, act, shapes in points:
        for (e, c, k, n) in shapes:
            timings = dispatch.autotune(c, k, n, act, reps=reps,
                                        cache=cache, save=False,
                                        mu=cfg.mu, e=e)
            best = min(timings, key=timings.get)
            base = _einsum_baseline_us(e, c, k, n, cfg.dtype, reps=reps)
            out.append({
                "op_point": op_point,
                "shape": {"E": e, "C": c, "K": k, "N": n},
                "kernels": {nm: round(us, 2) for nm, us in timings.items()},
                "best": best, "best_us": round(timings[best], 2),
                "einsum_baseline_us": round(base, 2),
                "speedup_vs_einsum": round(base / timings[best], 3),
            })
    return out


def collect(*, smoke: bool = True, reps: int = 3) -> dict:
    from repro.configs.registry import get_config, get_smoke_config
    from repro.models.decode import layer_grouped_matmul_shapes

    cache = dispatch.get_autotune_cache()
    cfg = get_smoke_config(MOE_ARCH) if smoke else get_config(MOE_ARCH)
    e, c, k, n = layer_grouped_matmul_shapes(cfg, DECODE_BATCH)[0]
    results = {
        "schema_version": 1,
        "backend": jax.default_backend(),
        "smoke": bool(smoke),
        "arch": MOE_ARCH,
        "dense": bench_dense(cache, reps=reps),
        # same dense problem with pre-quantized int8 activations (W1.58A8)
        "dense_int8": bench_dense(cache, reps=reps, act="int8"),
        "grouped": bench_grouped(cache, smoke=smoke, reps=reps),
        "a8_bytes": a8_bytes_moved(e=e, c=c, k=k, n=n, mu=cfg.mu),
    }
    cache.save()  # bench timings double as autotune measurements
    return results


def run():
    """CSV-row adapter for ``benchmarks/run.py``."""
    backend = jax.default_backend()
    results = collect(smoke=True)
    rows = []
    d = results["dense"]
    B, K, O = d["shape"]["M"], d["shape"]["K"], d["shape"]["N"]
    for name, us in sorted(d["kernels"].items(), key=lambda kv: kv[1]):
        spec = dispatch.get_kernel(name)
        tag = "pallas interpret" if (spec.pallas and backend != "tpu") else "xla"
        rows.append((f"kernel_{name}", us, f"B{B}xO{O}xN{K} via dispatch ({tag})"))

    auto = dispatch.select_kernel(B, K, O, "float32", policy="auto")
    rows.append(("dispatch_auto_choice", 0.0,
                 f"cache best={d['best']}; policy=auto -> {auto.name}"))

    for g in results["grouped"]:
        s = g["shape"]
        rows.append((f"grouped_{g['op_point']}_E{s['E']}C{s['C']}K{s['K']}N{s['N']}",
                     g["best_us"],
                     f"best={g['best']}; {g['speedup_vs_einsum']}x vs "
                     f"full-dequant einsum ({g['einsum_baseline_us']}us)"))

    # bandwidth story: bytes per weight streamed per matmul
    bf16_bytes = O * K * 2
    packed_bytes = O * -(-K // encoding.TRITS_PER_BYTE)
    rows.append(("weight_bytes_ratio_bf16_over_packed",
                 0.0, f"{bf16_bytes / packed_bytes:.1f}x fewer HBM bytes "
                      f"({packed_bytes} vs {bf16_bytes})"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-scale MoE dims (CI mode)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    enable_compile_cache()
    results = collect(smoke=args.smoke, reps=args.reps)
    for g in results["grouped"]:
        s = g["shape"]
        print(f"[kernel_bench] grouped {g['op_point']:>7} "
              f"E{s['E']} C{s['C']} K{s['K']} N{s['N']}: best={g['best']} "
              f"{g['best_us']:.0f}us vs einsum {g['einsum_baseline_us']:.0f}us "
              f"-> {g['speedup_vs_einsum']:.2f}x")
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[kernel_bench] wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
