"""Serving launcher: load (or initialize) a model, quantize to the packed
1.6-bit artifact, and serve batched generation through the
continuous-batching scheduler (default) or the generational baseline.

On a pod this runs one process per host against the production mesh; on this
container it exercises the identical code path on local devices.

Usage:
  python -m repro.launch.serve --arch bitnet-b1.58-2b --smoke \
      [--ckpt-dir DIR] [--batch 4] [--new-tokens 32] [--temperature 0.8] \
      [--discipline continuous|generational] [--stream] \
      [--prefill-chunk 32] [--admission-budget 1] [--mesh 1x8] \
      [--prefix-cache] [--prefix-cache-mb 64] \
      [--draft qwen3-0.6b] [--spec-k 4] [--dynamic-spec-k] \
      [--scenario chat|rag|agentic|code] [--scenario-seed 0]

``--scenario NAME`` replaces the fixed request list with a named
multi-tenant workload (see ``repro.serving.workload``) replayed open-loop
under the wall clock: requests arrive on each tenant's stochastic arrival
process, queue for real, and the launcher prints per-tenant p50/p95/p99
TTFT+TPOT plus SLO attainment.  The deterministic virtual-clock variant
(for CI-diffable numbers and saturation sweeps) lives in
``benchmarks/serving_bench.py --scenario``.

``--draft <arch>`` turns on draft-and-verify speculative decoding on the
continuous path: the (replicated, randomly-initialized here — pass a real
draft checkpoint in deployment) draft model proposes ``--spec-k - 1``
greedy continuations per scheduler step and the target verifies all
candidates in one batched forward, emitting the accepted window.  Greedy
streams are byte-identical to non-speculative serving under the canonical
(bf16-argmax) greedy selection the speculative round is defined over
(``SamplerConfig(canonical_greedy=True)`` on the non-spec engine; on dense
caches the verify forward itself is scatter-first bitwise-exact); the
draft and target must share a tokenizer/vocab (the engine raises
ValueError otherwise) and ``--temperature`` must stay 0.

``--mesh DxM`` (e.g. ``1x8``) serves sharded: packed ternary weights are
tensor-parallel on the ``model`` axis and MoE expert stacks expert-parallel
on ``data`` (rules in ``repro/parallel/sharding.py``), the KV/state cache is
sharded alongside, and kernel dispatch keys its autotune cache on the
per-shard local problems.  The axis product must match the device count —
on CPU, force devices with XLA_FLAGS=--xla_force_host_platform_device_count=8.

Admission is chunked and length-bucketed on supported architectures:
prompts are padded to a multiple of ``--prefill-chunk`` and prefilled one
fixed-shape chunk at a time (one compiled trace for any prompt-length mix);
``--admission-budget`` caps prefill chunks per scheduler step so co-batched
requests keep decoding — bounded time-to-first-token — while a long prompt
is admitted.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import checkpointing as ckpt
from repro.configs.registry import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.decode import packed_bits_per_weight, quantize_for_serving
from repro.models.model import init_params
from repro.serving.engine import DecodeEngine, Request, SamplerConfig
from repro.serving.scheduler import ContinuousScheduler


def describe_devices() -> dict:
    """The devices JAX runs on, as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_served(cfg, *, seed: int = 0, ckpt_dir: str | None = None):
    """Initialize params from ``seed`` (or restore the latest checkpoint in
    ``ckpt_dir``) and quantize them to the packed serving artifact.

    Each path is one jitted program: op by op, init and quantization
    compile one executable per op and keep every f32 temporary of the
    ternarize/pack passes alive at once (13.8 GB peak for bitnet-b1.58-2b
    on a 16 GB v5e)."""
    key = jax.random.PRNGKey(seed)
    if ckpt_dir:
        step, state = ckpt.restore_latest(
            ckpt_dir, {"params": jax.eval_shape(lambda: init_params(cfg, key))})
        if state is not None:
            print(f"[serve] restored step {step} from {ckpt_dir}")
            return jax.jit(lambda p: quantize_for_serving(p, cfg))(
                state["params"])
    return jax.jit(lambda k: quantize_for_serving(init_params(cfg, k), cfg))(
        key)


def serve_requests(engine: DecodeEngine, reqs: list[Request], *,
                   discipline: str = "continuous", on_token=None,
                   admission_budget: int | None = None,
                   dynamic_spec_k: bool = False):
    """Serve ``reqs`` to completion on ``engine``; their ``out`` lists fill
    in place.  Returns ``(decode_steps, scheduler)`` — the scheduler is
    None on the generational path."""
    if discipline == "generational":
        engine.run(reqs)
        return max(len(r.out) for r in reqs), None
    sched = ContinuousScheduler(engine, on_token=on_token,
                                admission_budget=admission_budget,
                                dynamic_spec_k=dynamic_spec_k)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched.stats.decode_steps, sched


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", help="restore trained params (else random init)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests (default: --batch; may exceed "
                    "it — the scheduler queues and refills slots)")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="admission prefill chunk size / bucket granularity "
                    "(clamped to the ring on windowed configs)")
    ap.add_argument("--admission-budget", type=int, default=0,
                    help="max prefill chunks per scheduler step (0 = "
                    "unbounded); >0 bounds co-batched time-to-first-token "
                    "while long prompts are admitted (continuous only)")
    ap.add_argument("--discipline", choices=["continuous", "generational"],
                    default="continuous")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are emitted (continuous only)")
    ap.add_argument("--mesh", default=None,
                    help="serve sharded over a DxM (data x model) device "
                    "mesh, e.g. 1x8 (TP) or 2x4 (EP x TP); axis product "
                    "must equal the device count")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hashed shared-prefix KV reuse: admission splices "
                    "cached KV blocks (block = one --prefill-chunk) instead "
                    "of recomputing them, publishes fresh blocks, and the "
                    "scheduler admits cache-hot requests first (continuous "
                    "only; chunked-admission archs)")
    ap.add_argument("--prefix-cache-mb", type=float, default=64.0,
                    help="prefix-cache byte budget in MiB (LRU eviction)")
    ap.add_argument("--draft", default=None,
                    help="draft arch for speculative decoding (continuous "
                    "only, greedy only; must share the target's "
                    "tokenizer/vocab — mismatches raise ValueError)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="candidates per speculative verify step (1 free "
                    "target token + spec-k - 1 drafted)")
    ap.add_argument("--act-dtype", choices=["none", "int8"], default="none",
                    help="activation dtype for the packed ternary "
                    "projections: int8 quantizes per token (absmax) in "
                    "front of every packed matmul — the W1.58A8 end-to-end "
                    "path (dispatch routes w2a8/grouped_w2a8/tl2)")
    ap.add_argument("--scenario", default=None,
                    help="replay a named multi-tenant workload (chat | rag "
                    "| agentic | code) open-loop under the WALL clock "
                    "instead of the fixed request list, and print "
                    "per-tenant p50/p95/p99 TTFT+TPOT and SLO attainment "
                    "(continuous only; --smoke shrinks the scenario too)")
    ap.add_argument("--scenario-seed", type=int, default=0,
                    help="arrival-trace seed for --scenario")
    ap.add_argument("--dynamic-spec-k", action="store_true",
                    help="with --draft: size each request's next draft "
                    "window from its measured acceptance, clamped to "
                    "[2, --spec-k]")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    print(f"[serve] devices {describe_devices()}; compile cache {cache_dir}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.act_dtype != "none":
        cfg = cfg.with_(act_dtype=args.act_dtype)
    served = load_served(cfg, ckpt_dir=args.ckpt_dir)
    print(f"[serve] {cfg.name}: packed {packed_bits_per_weight(served):.3f} b/w")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
        print(f"[serve] mesh {args.mesh}: "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    draft = None
    if args.draft:
        if args.discipline != "continuous":
            raise SystemExit("[serve] --draft requires --discipline "
                             "continuous (the generational path ignores "
                             "the draft)")
        dcfg = (get_smoke_config(args.draft) if args.smoke
                else get_config(args.draft))
        draft_params = load_served(dcfg, seed=1)
        print(f"[serve] draft {dcfg.name}: spec_k={args.spec_k}, packed "
              f"{packed_bits_per_weight(draft_params):.3f} b/w")
        draft = (draft_params, dcfg)
    scenario = None
    if args.scenario:
        if args.discipline != "continuous":
            raise SystemExit("[serve] --scenario requires --discipline "
                             "continuous (open-loop arrivals need slot "
                             "refills)")
        from repro.serving.workload import get_scenario

        scenario = get_scenario(args.scenario)
        if args.smoke:
            scenario = scenario.smoke()
        need = scenario.max_prompt_len() + scenario.max_new_tokens() + 1
        if args.max_len < need:  # the scenario dictates the geometry
            args.max_len = -(-need // 16) * 16
            print(f"[serve] scenario {scenario.name}: max_len raised to "
                  f"{args.max_len}")
    engine = DecodeEngine(served, cfg, batch_size=args.batch,
                          max_len=args.max_len,
                          sampler=SamplerConfig(temperature=args.temperature,
                                                top_k=args.top_k),
                          prefill_chunk=args.prefill_chunk, mesh=mesh,
                          prefix_cache=args.prefix_cache,
                          prefix_cache_mb=args.prefix_cache_mb,
                          draft=draft, spec_k=args.spec_k)
    for (entry, role, m, k, n), name in engine.kernel_choices().items():
        print(f"[serve] kernel {entry} {role} M{m} K{k} N{n}: {name}")
    budget = args.admission_budget if args.admission_budget > 0 else None
    if scenario is not None:
        from repro.serving.loadgen import (LoadGenerator, generate_trace,
                                           latency_summary)

        trace = generate_trace(scenario, cfg.vocab_size, args.scenario_seed)
        gen = LoadGenerator(engine, trace, clock="wall",
                            admission_budget=budget,
                            dynamic_spec_k=args.dynamic_spec_k)
        res = gen.run()
        print(f"[serve] scenario {scenario.name} (seed "
              f"{args.scenario_seed}): {len(res.records)} requests, "
              f"offered {res.offered_qps:.2f} qps, achieved "
              f"{res.achieved_qps:.2f} qps, makespan {res.makespan_s:.2f}s")
        tenants = {t.name: t for t in scenario.tenants}
        for name, recs in sorted(res.by_tenant().items()):
            ttft = latency_summary(
                [r.ttft_s for r in recs if r.ttft_s is not None], 4)
            tpot = latency_summary(
                [r.tpot_s for r in recs if r.tpot_s is not None], 4)
            ten = tenants[name]
            ok = sum(1 for r in recs
                     if r.ttft_s is not None and r.ttft_s <= ten.slo_ttft_s
                     and (r.tpot_s is None or r.tpot_s <= ten.slo_tpot_s))
            print(f"[serve]   {name}: {len(recs)} reqs | ttft p50/p95/p99 "
                  f"{ttft['p50']}/{ttft['p95']}/{ttft['p99']}s | tpot p50 "
                  f"{tpot['p50']}s | slo attainment {ok / len(recs):.0%}")
        return

    n_req = args.requests if args.requests is not None else args.batch
    reqs = [Request(prompt=[7 + i, 13 + i], max_new_tokens=args.new_tokens)
            for i in range(n_req)]

    if args.discipline == "generational" and n_req > args.batch:
        raise SystemExit("[serve] generational cannot queue: "
                         "--requests must be <= --batch")
    ids = {r.rid: i for i, r in enumerate(reqs)}
    on_token = (lambda r, t: print(f"  [stream] req {ids[r.rid]}: {t}")) \
        if args.stream else None
    t0 = time.time()
    steps, sched = serve_requests(
        engine, reqs, discipline=args.discipline, on_token=on_token,
        admission_budget=budget,
        dynamic_spec_k=args.dynamic_spec_k)
    dt = time.time() - t0
    n = sum(len(r.out) for r in reqs)
    print(f"[serve] {args.discipline}: {n} tokens / {steps} decode steps "
          f"in {dt:.1f}s ({n / dt:.1f} tok/s)")
    if args.draft and sched is not None:
        st = sched.stats
        print(f"[serve] speculative: {st.spec_rounds} rounds, "
              f"{st.accepted_drafted_tokens}/{st.drafted_tokens} drafted "
              f"tokens accepted ({st.acceptance_rate:.0%}), "
              f"{n / max(st.decode_steps, 1):.2f} tok/decode-step")
    if engine.prefix_store is not None:
        st = engine.prefix_store.stats
        print(f"[serve] prefix cache: {st.hit_blocks}/{st.lookups} block "
              f"hits ({st.hit_rate:.0%}), {st.reused_tokens} prompt tokens "
              f"spliced, {len(engine.prefix_store)} blocks resident "
              f"({engine.prefix_store.nbytes >> 10} KiB)")
    for i, r in enumerate(reqs):
        print(f"  [{i}] {r.out}")


if __name__ == "__main__":
    main()
