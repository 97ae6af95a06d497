"""Serving throughput: continuous-batching scheduler vs generational batching
on a skewed workload — the case where generational batching collapses
(every batch turns over at the pace of its slowest request, so a few long
requests leave most slots idle most of the time).

Bitnet.cpp and TENET report end-to-end ternary decode tok/s as the headline
metric; this benchmark seeds the same trajectory for this repo.  Both paths
run the identical packed-ternary model through the identical jitted
decode_step — only the batching discipline differs — so the ratio isolates
scheduling, not kernels.

The workload is skewed along two axes: token budgets (many short + few long
generations: generational idle-slot collapse) and prompt lengths (every
``--long-prompt-every``-th request carries a ``--long-prompt-len`` prompt:
admission latency).  Besides tok/s, the bench records per-request
**time-to-first-token** — continuous admission is chunked (fixed-size
prefill chunks, one compiled trace) and budgeted (``--admission-budget``
chunks per scheduler step), so co-batched requests keep decoding while a
long prompt is admitted and their TTFT stays bounded.

With ``--prefix-cache`` the bench additionally runs the **shared-prefix
workload** — N requests sharing a long system prompt, mixed with unique
cold prompts, the traffic shape prefix caching exists for (cf. the
``precise-prefix-cache-aware`` scenario in llm-d-benchmark) — twice: a cold
engine with no store (recompute-from-scratch baseline) and a warm engine
whose ``PrefixBlockStore`` was pre-populated by a full warmup pass.  It
reports the block ``prefix_hit_rate`` of the measured warm pass, TTFT split
by shared vs cold requests, the warm/cold shared-TTFT improvement, and the
scheduler's per-request queue-wait summary (the fairness cost of
cache-affinity admission reordering, measurable next to the TTFT it buys).

With ``--draft <arch>`` the bench additionally runs the **speculative**
section: a small ternary draft model proposes ``--spec-k - 1`` greedy
continuations per round and the target verifies all candidates in one
batched forward (``DecodeEngine(draft=..., spec_k=...)``).  The section is
self-contained — the main workload is admission-heavy by design, so
speculation (a decode optimization) runs its own decode-heavy workload
through a **zero-tail twin** pair: a ≥8-layer target whose tail layers'
output scales are zeroed post-quantization (exact no-ops — the deep model
computes its 1-layer slice's function at full L-layer cost) and a draft
that IS that first layer, so drafting is ~L× cheaper and acceptance is
1.0 by construction.  Both a plain continuous baseline and the
speculative engine run the same workload; the streams are compared
byte-for-byte (greedy speculation must change *how many steps* the tokens
take, never the tokens — dense verify is scatter-first bitwise-exact, and
both engines use the canonical bf16-argmax greedy selection the
speculative round is defined over) and the tok/s ratio is the per-round
amortization win at the acceptance ceiling.  Real drafts accept less; the
section is labeled ``twin_draft``.

Writes ``BENCH_serving.json`` (schema below) for CI to surface in PRs:

  {"schema_version": 4, "arch": ..., "batch": ..., "workload": {...},
   "prefill_chunk": C, "admission_budget": k, "mesh": "1x8" | null,
   "generational": {"tokens": N, "seconds": s, "tok_s": r, "decode_steps": d,
                    "ttft_s": {"mean": m, "p50": p, "p95": q, "p99": Q,
                               "max": M},
                    "tpot_s": {... same percentile keys ...}},
   "continuous":   {... same keys, plus "admission_steps"/"sched_steps"
                    and "queue_wait_s" mean/p50/max ...},
   "speedup": continuous.tok_s / generational.tok_s,
   "ttft_ratio": continuous.ttft_s.max / generational.ttft_s.max,
   "prefix": {"enabled": bool, ...with --prefix-cache:
              "workload": {...}, "cold": {...}, "warm": {...},
              "prefix_hit_rate": h, "ttft_improvement":
              cold.shared_ttft_s.mean / warm.shared_ttft_s.mean},
   "speculative": {"enabled": bool, ...with --draft:
                   "draft": name, "spec_k": K, "twin_draft": true,
                   "target_layers": L, "workload": {"requests": n,
                   "new_tokens": t},
                   "tokens"/"seconds"/"tok_s"/"decode_steps"/"ttft_s"/
                   "tpot_s" as above, "spec_rounds": n,
                   "acceptance_rate": a, "drafted_tokens": D,
                   "accepted_drafted_tokens": A,
                   "tokens_per_decode_step": tokens / decode_steps,
                   "baseline_tok_s": r (the section's own non-spec run),
                   "speedup": tok_s / baseline_tok_s,
                   "byte_identical": spec stream == baseline stream}}

Schema v4 is v3 plus the ``speculative`` section, ``ttft_s`` tail
percentiles (p95/p99), and the per-request ``tpot_s``
(time-per-output-token) summary; v3 was v2 plus the ``prefix`` section
and the continuous path's ``queue_wait_s``.  Every pre-existing field is
unchanged, so older consumers (and the CI field-presence check, which
accepts v2+) keep working on old files.

``decode_steps`` counts steps that ran a decode; the continuous path's
admission-only steps (prompts still prefilling, nothing live to decode) are
reported separately as ``admission_steps``.  ``--mesh DxM`` runs both paths
on a sharded engine (TP on model, MoE EP on data) over forced host devices.

Run:  PYTHONPATH=src python benchmarks/serving_bench.py --smoke
      (CPU-friendly reduced config; full mode uses the registry smoke config
      unreduced).  Compile time is excluded via a warmup pass; the chunked
      admission path compiles one trace per chunk size regardless of the
      prompt-length mix.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import analysis  # noqa: E402  (benchmarks/analysis.py, same directory)

from repro.configs.registry import get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.decode import quantize_for_serving
from repro.models.model import init_params
from repro.serving.engine import DecodeEngine, Request, SamplerConfig
from repro.serving.loadgen import (LoadGenerator, generate_trace,
                                   latency_summary, percentile)
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.workload import get_scenario


def make_requests(n: int, short_new: int, long_new: int, long_every: int,
                  prompt_len: int, long_prompt_len: int,
                  long_prompt_every: int, vocab: int,
                  seed: int = 0) -> list[Request]:
    """Doubly skewed workload, drawn from a seeded rng rather than one
    hardcoded list: exactly ``n // long_every`` requests generate
    ``long_new`` tokens (vs ``short_new``) and exactly
    ``n // long_prompt_every`` carry a ``long_prompt_len`` prompt (vs
    ``prompt_len``) — the admission-latency case — but their *positions*
    in the arrival order and the prompt token *content* are sampled.  The
    same seed reproduces the same workload byte-for-byte (the CI
    tokens-equality check across batching paths relies on the exact
    counts), while different seeds give genuinely different skew mixes."""
    rng = np.random.default_rng([seed, 0x5EED])
    budgets = np.full(n, short_new, np.int64)
    budgets[rng.choice(n, n // long_every, replace=False)] = long_new
    plens = np.full(n, prompt_len, np.int64)
    plens[rng.choice(n, n // long_prompt_every, replace=False)] = \
        long_prompt_len
    return [Request(prompt=[int(t) for t in
                            rng.integers(2, vocab - 1, size=int(plens[i]))],
                    max_new_tokens=int(budgets[i]))
            for i in range(n)]


def make_shared_prefix_requests(n: int, prefix_len: int, suffix_len: int,
                                cold_every: int, cold_prompt_len: int,
                                new_tokens: int, vocab: int,
                                salt: int = 0) -> list[Request]:
    """Prefix-cache traffic shape: most requests share one long system
    prompt (plus a short unique suffix), every ``cold_every``-th request is
    a unique cold prompt.  ``salt`` varies the *unique* parts between runs
    so cold prompts never accidentally warm-hit across passes; the shared
    prefix is deliberately salt-independent."""
    shared = [2 + ((11 * j) % (vocab - 3)) for j in range(prefix_len)]
    reqs = []
    for i in range(n):
        cold = cold_every > 0 and i % cold_every == cold_every - 1
        if cold:
            prompt = [2 + ((5 * (i + 131 * salt) + 3 * j) % (vocab - 3))
                      for j in range(cold_prompt_len)]
        else:
            prompt = shared + [2 + ((7 * (i + 131 * salt) + j) % (vocab - 3))
                               for j in range(suffix_len)]
        r = Request(prompt=prompt, max_new_tokens=new_tokens)
        r.shared = not cold  # bench-side tag for the TTFT split
        reqs.append(r)
    return reqs


def _ttft_summary(vals: list[float]) -> dict:
    """mean/p50/p95/p99/max over per-request latencies (TTFT or TPOT) —
    tail percentiles included because speculation (and admission budgeting)
    claims are about the tail, not the mean.  Delegates to the repo's
    shared estimator (linear-interpolation percentiles, cross-checked
    against numpy in tests/test_workload.py)."""
    return latency_summary(vals, ndigits=4)


def _tpot_summary(token_times: dict[int, list[float]]) -> dict:
    """Per-request TPOT (time per output token: emission span / (n - 1))
    summarized across requests; single-token requests carry no inter-token
    gap and are excluded.  Speculative rounds emit their accepted window in
    one burst — those tokens share a timestamp, which is exactly the point:
    TPOT measures what a streaming client observes."""
    tpots = [(ts[-1] - ts[0]) / (len(ts) - 1)
             for ts in token_times.values() if len(ts) > 1]
    return _ttft_summary(tpots) if tpots else {}


def run_shared_prefix(engine: DecodeEngine, reqs: list[Request],
                      admission_budget: int | None) -> dict:
    """One pass of the shared-prefix workload with per-request TTFT split
    by shared vs cold, plus the scheduler queue-wait summary."""
    first_tok: dict[int, float] = {}

    def stamp(req, tok):
        first_tok.setdefault(req.rid, time.perf_counter())

    for r in reqs:
        r.on_token = stamp
    sched = ContinuousScheduler(engine, admission_budget=admission_budget)
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.run(max_steps=100_000)
    dt = time.perf_counter() - t0
    ttft = {r.rid: first_tok[r.rid] - t0 for r in reqs}
    assert len(ttft) == len(reqs), "a request never emitted a first token"
    return {"tokens": sum(len(r.out) for r in reqs),
            "seconds": round(dt, 4),
            "ttft_s": _ttft_summary(list(ttft.values())),
            "shared_ttft_s": _ttft_summary(
                [ttft[r.rid] for r in reqs if r.shared]),
            "cold_ttft_s": _ttft_summary(
                [ttft[r.rid] for r in reqs if not r.shared]),
            "prefill_chunks": sched.stats.prefill_chunks,
            "affinity_reorders": sched.stats.affinity_reorders,
            "queue_wait_s": {k: round(v, 4) for k, v in
                             sched.stats.queue_wait_summary().items()}}


def bench_prefix(args, cfg, served, mesh, budget) -> dict:
    """Shared-prefix workload, cold vs warm: a no-store engine (recompute
    baseline) vs a prefix-cache engine whose store was populated by a full
    warmup pass.  The measured warm pass's block hit rate and shared-request
    TTFT improvement are the headline numbers."""
    from repro.serving.prefix_cache import PrefixStoreStats

    max_len = max(args.shared_prefix_len + args.shared_suffix_len,
                  args.cold_prompt_len) + args.shared_new + 1

    def mk(salt):
        return make_shared_prefix_requests(
            args.shared_requests, args.shared_prefix_len,
            args.shared_suffix_len, args.cold_every, args.cold_prompt_len,
            args.shared_new, cfg.vocab_size, salt=salt)

    def engine(prefix_cache):
        return DecodeEngine(served, cfg, batch_size=args.batch,
                            max_len=max_len, matmul_policy=args.policy,
                            prefill_chunk=args.prefill_chunk, mesh=mesh,
                            prefix_cache=prefix_cache,
                            prefix_cache_mb=args.prefix_cache_mb)

    e_cold = engine(False)
    run_shared_prefix(e_cold, mk(0), budget)  # warmup: compile
    cold = run_shared_prefix(e_cold, mk(1), budget)

    e_warm = engine(True)
    run_shared_prefix(e_warm, mk(2), budget)  # warmup: compile + publish
    e_warm.prefix_store.stats = PrefixStoreStats()  # measure one pass only
    warm = run_shared_prefix(e_warm, mk(3), budget)
    st = e_warm.prefix_store.stats

    out = {"enabled": True,
           "workload": {"requests": args.shared_requests,
                        "shared_prefix_len": args.shared_prefix_len,
                        "shared_suffix_len": args.shared_suffix_len,
                        "cold_every": args.cold_every,
                        "cold_prompt_len": args.cold_prompt_len,
                        "shared_new": args.shared_new},
           "cold": cold, "warm": warm,
           "prefix_hit_rate": round(st.hit_rate, 4),
           "hit_blocks": st.hit_blocks, "miss_blocks": st.miss_blocks,
           "reused_tokens": st.reused_tokens,
           "ttft_improvement": round(
               cold["shared_ttft_s"]["mean"]
               / max(warm["shared_ttft_s"]["mean"], 1e-9), 3)}
    print(f"[serving_bench] shared-prefix cold: shared ttft mean "
          f"{cold['shared_ttft_s']['mean']:.3f}s, cold-req mean "
          f"{cold['cold_ttft_s']['mean']:.3f}s")
    print(f"[serving_bench] shared-prefix warm: shared ttft mean "
          f"{warm['shared_ttft_s']['mean']:.3f}s, hit rate "
          f"{st.hit_rate:.0%} ({st.hit_blocks}/{st.lookups} blocks, "
          f"{st.reused_tokens} tokens spliced), ttft improvement "
          f"{out['ttft_improvement']:.2f}x")
    return out


def run_generational(engine: DecodeEngine, reqs: list[Request]) -> dict:
    """Seed baseline: batches of B run to the slowest request, sequentially."""
    steps = 0
    for i in range(0, len(reqs), engine.B):
        chunk = reqs[i:i + engine.B]
        engine.run(chunk)
        steps += max(len(r.out) for r in chunk)
    return {"decode_steps": steps}


def run_continuous(engine: DecodeEngine, reqs: list[Request],
                   admission_budget: int | None = None) -> dict:
    sched = ContinuousScheduler(engine, admission_budget=admission_budget)
    for r in reqs:
        sched.submit(r)
    sched.run(max_steps=100_000)
    # decode_steps counts steps that ran a decode; admission-only steps
    # (all slots still prefilling) are tallied separately so tok/step stays
    # an honest decode metric
    out = {"decode_steps": sched.stats.decode_steps,
           "admission_steps": sched.stats.admission_steps,
           "sched_steps": sched.stats.steps,
           "queue_wait_s": {k: round(v, 4) for k, v in
                            sched.stats.queue_wait_summary().items()}}
    if sched.stats.spec_rounds:
        out.update(
            spec_rounds=sched.stats.spec_rounds,
            drafted_tokens=sched.stats.drafted_tokens,
            accepted_drafted_tokens=sched.stats.accepted_drafted_tokens,
            acceptance_rate=round(sched.stats.acceptance_rate, 4))
    return out


def bench(path_fn, engine, mk_reqs) -> tuple[dict, list[list[int]]]:
    """Measure one batching path: warmup pass (compile), then a timed pass
    with per-token timestamps keyed on ``Request.rid``.  Returns the metric
    dict AND the emitted token streams in request order — the speculative
    section's byte-identity gate compares streams across paths."""
    path_fn(engine, mk_reqs())  # warmup: compile prefill chunks + decode step
    reqs = mk_reqs()
    token_times: dict[int, list[float]] = {}

    def stamp(req, tok):
        token_times.setdefault(req.rid, []).append(time.perf_counter())

    for r in reqs:
        r.on_token = stamp
    t0 = time.perf_counter()
    step_stats = path_fn(engine, reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in reqs)
    assert all(r.done or len(r.out) == r.max_new_tokens for r in reqs)
    ttft = [token_times[r.rid][0] - t0 for r in reqs if r.rid in token_times]
    assert len(ttft) == len(reqs), "a request never emitted a first token"
    return ({"tokens": tokens, "seconds": round(dt, 4),
             "tok_s": round(tokens / dt, 2), **step_stats,
             "ttft_s": _ttft_summary(ttft),
             "tpot_s": _tpot_summary(token_times)},
            [list(r.out) for r in reqs])


def _zero_tail_wo(d: dict, under_wo: bool = False) -> dict:
    """Zero the ``wo``-projection scales of every layer but the first, on a
    stacked-blocks param tree.  A packed ternary projection contributes
    ``scale * (packed_matmul)`` to the residual stream, so zeroed tail
    scales make layers 1..L-1 exact no-ops: the L-layer model *computes the
    same function* as its 1-layer slice while paying L layers of real
    ternary compute."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out[k] = _zero_tail_wo(v, under_wo=(k == "wo"))
        elif under_wo and k == "scale":
            out[k] = v.at[1:].set(0)
        else:
            out[k] = v
    return out


def make_spec_pair(args, cfg):
    """Build the speculative section's (target, draft) pair: a **zero-tail
    function twin**.

    The target is the bench config deepened to at least 8 layers, with the
    attention/FFN output scales of layers 1..L-1 zeroed after quantization —
    those layers' residual deltas are exactly 0.0, so the deep target
    computes the same function as its first layer alone while paying the
    full L-layer decode cost.  The draft is literally the target's first
    layer (``blocks`` sliced to ``[:1]``, shared embed/lm_head/final_norm)
    under the ``--draft`` arch's registry name, so drafting is ~L× cheaper
    than a target step and every greedy proposal matches the target's
    argmax — acceptance is 1.0 *by construction*.

    This makes the section a measurement of the speculative machinery's
    per-round amortization ceiling (fused K-token verify vs K sequential
    decode steps) and of the byte-identity guarantee, NOT of a realistic
    draft/target acceptance rate — real drafts accept less and the speedup
    scales with their acceptance.  The output is labeled ``twin_draft`` so
    downstream consumers can't mistake it for a trained-draft result."""
    from repro.configs.registry import get_config

    scfg = cfg.with_(n_layers=max(cfg.n_layers, 8))
    sparams = quantize_for_serving(init_params(scfg, jax.random.PRNGKey(0)),
                                   scfg)
    sparams = dict(sparams, blocks=_zero_tail_wo(sparams["blocks"]))
    dparams = dict(sparams,
                   blocks=jax.tree.map(lambda x: x[:1], sparams["blocks"]))
    # structural knobs stay the target's (the sliced params must parse);
    # the registry lookup resolves module-style aliases (qwen3_0p6b)
    dcfg = scfg.with_(n_layers=1, name=get_config(args.draft).name)
    return scfg, sparams, dparams, dcfg


def bench_speculative(args, cfg, mesh) -> dict:
    """Speculative continuous serving vs its own non-speculative baseline.

    Self-contained by design: the doubly-skewed main workload is admission-
    heavy (most requests generate 2 tokens), which would measure prefill
    overlap rather than speculation.  This section instead runs a
    decode-heavy workload (``--spec-requests`` × ``--spec-new`` tokens,
    short varied prompts) through TWO fresh engines built on the zero-tail
    twin pair (:func:`make_spec_pair`) — one plain continuous, one
    speculative — and reports: tok/s for both, acceptance rate, tokens per
    decode step (the claim: ≈ spec_k — each round retires its accepted
    window through ONE fused draft+verify+rollback call), and byte-identity
    of the greedy streams (must be True: dense verify is scatter-first
    exact, so speculation changes how many steps the tokens take, never the
    tokens)."""
    import numpy as np

    scfg, sparams, dparams, dcfg = make_spec_pair(args, cfg)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(2, scfg.vocab_size - 2,
                                             int(rng.integers(4, 11)))]
               for _ in range(args.spec_requests)]
    max_len = max(len(p) for p in prompts) + args.spec_new + 1
    max_len = -(-max_len // 16) * 16

    def mk_spec_reqs():
        return [Request(prompt=list(p), max_new_tokens=args.spec_new)
                for p in prompts]

    runs = {}
    outs = {}
    for name, draft in (("baseline", None), ("spec", (dparams, dcfg))):
        # canonical (bf16-argmax) greedy on BOTH engines: the speculative
        # round always selects canonically, so the baseline must too for
        # the streams to be byte-comparable
        engine = DecodeEngine(sparams, scfg, batch_size=args.batch,
                              max_len=max_len, matmul_policy=args.policy,
                              prefill_chunk=args.prefill_chunk, mesh=mesh,
                              sampler=SamplerConfig(canonical_greedy=True),
                              draft=draft,
                              spec_k=args.spec_k if draft else 2)
        runs[name], outs[name] = bench(
            lambda e, r: run_continuous(e, r), engine, mk_spec_reqs)
    spec = runs["spec"]
    out = {"enabled": True, "draft": dcfg.name, "spec_k": args.spec_k,
           "twin_draft": True, "target_layers": scfg.n_layers,
           "workload": {"requests": args.spec_requests,
                        "new_tokens": args.spec_new}, **spec,
           "tokens_per_decode_step": round(
               spec["tokens"] / max(spec["decode_steps"], 1), 3),
           "baseline_tok_s": runs["baseline"]["tok_s"],
           "speedup": round(spec["tok_s"] / runs["baseline"]["tok_s"], 3),
           "byte_identical": outs["spec"] == outs["baseline"]}
    print(f"[serving_bench]  speculative: {spec['tokens']} tok in "
          f"{spec['seconds']:.2f}s = {spec['tok_s']:.1f} tok/s vs baseline "
          f"{out['baseline_tok_s']:.1f} tok/s ({spec['decode_steps']} decode "
          f"steps, {out['tokens_per_decode_step']:.2f} tok/step, acceptance "
          f"{spec.get('acceptance_rate', 0.0):.0%}, speedup "
          f"{out['speedup']:.2f}x, byte-identical: "
          f"{out['byte_identical']})")
    return out


def bench_scenario(args, cfg, served, mesh, budget) -> tuple[dict, dict | None]:
    """Replay a named multi-tenant scenario through the load generator and
    report the schema-v5 ``workload`` section (per-tenant p50/p95/p99
    TTFT+TPOT, SLO attainment, goodput) plus, with ``--saturate``, the
    doubling+bisection sweep for max sustainable QPS.

    One engine serves every probe (same compiled traces; scaling changes
    arrival rates, never shapes).  Under the default virtual clock each run
    is fully deterministic — same seed, byte-identical ``workload`` section
    — and compile time cannot pollute the metrics; ``--clock wall``
    measures real time instead (a warmup replay absorbs compilation)."""
    scenario = get_scenario(args.scenario)
    if args.smoke:
        scenario = scenario.smoke()
    if args.qps_scale != 1.0:
        scenario = scenario.scaled(args.qps_scale)
    max_len = scenario.max_prompt_len() + scenario.max_new_tokens() + 1
    max_len = -(-max_len // 16) * 16
    engine = DecodeEngine(served, cfg, batch_size=args.batch,
                          max_len=max_len, matmul_policy=args.policy,
                          prefill_chunk=args.prefill_chunk, mesh=mesh,
                          prefix_cache=args.prefix_cache,
                          prefix_cache_mb=args.prefix_cache_mb)

    def run_at(scale: float, clock: str):
        sc = scenario.scaled(scale) if scale != 1.0 else scenario
        trace = generate_trace(sc, cfg.vocab_size, args.seed)
        gen = LoadGenerator(engine, trace, clock=clock,
                            decode_step_cost_s=args.step_cost_decode,
                            prefill_chunk_cost_s=args.step_cost_prefill,
                            admission_budget=budget)
        return sc, gen.run()

    if args.clock == "wall":
        run_at(1.0, "wall")  # warmup: compile every chunk/step trace
    sc, result = run_at(1.0, args.clock)
    workload = analysis.scenario_report(sc, result, args.seed)
    for name, t in workload["tenants"].items():
        print(f"[serving_bench] scenario {scenario.name}/{name}: "
              f"{t['requests']} reqs, ttft p50/p99 {t['ttft_s']['p50']:.4f}/"
              f"{t['ttft_s']['p99']:.4f}s, tpot p50 {t['tpot_s']['p50']:.4f}"
              f"s, slo attainment {t['slo_attainment']:.0%}")
    print(f"[serving_bench] scenario {scenario.name}: offered "
          f"{workload['offered_qps']:.2f} qps, achieved "
          f"{workload['achieved_qps']:.2f} qps, overall attainment "
          f"{workload['slo_attainment']:.0%}, goodput "
          f"{workload['goodput_qps']:.2f} qps")
    saturation = None
    if args.saturate:

        def p99_at(scale):
            _, res = run_at(scale, "virtual")
            return percentile([r.ttft_s for r in res.records
                               if r.ttft_s is not None], 99)

        saturation = analysis.saturation_sweep(
            p99_at, scenario.offered_qps(), scenario.slo_ttft_budget(),
            max_doublings=args.saturate_doublings,
            bisect_iters=args.saturate_bisects, log=print)
        print(f"[serving_bench] scenario {scenario.name}: max sustainable "
              f"{saturation['max_sustainable_qps']:.2f} qps at p99 ttft <= "
              f"{saturation['slo_ttft_s']}s "
              f"({'bracketed' if saturation['saturated'] else 'lower bound'}"
              f", {len(saturation['probes'])} probes)")
    return workload, saturation


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-b1.58-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-friendly reduction (CI mode)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--short-new", type=int, default=2)
    ap.add_argument("--long-new", type=int, default=32)
    ap.add_argument("--long-every", type=int, default=4,
                    help="every k-th request is long (generation-skew knob)")
    ap.add_argument("--prompt-len", type=int, default=3)
    ap.add_argument("--long-prompt-len", type=int, default=48,
                    help="prompt length of the long-prompt requests "
                    "(admission-skew knob)")
    ap.add_argument("--long-prompt-every", type=int, default=5,
                    help="every k-th request has a long prompt")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="admission prefill chunk size (bucket granularity)")
    ap.add_argument("--admission-budget", type=int, default=1,
                    help="prefill chunks per scheduler step for the "
                    "continuous path (0 = unbounded)")
    ap.add_argument("--policy", default="auto",
                    help="ternary-matmul dispatch policy for both paths")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="also run the shared-prefix workload cold vs warm "
                    "and report prefix_hit_rate + shared-request TTFT "
                    "improvement in a schema-v3 'prefix' section")
    ap.add_argument("--prefix-cache-mb", type=float, default=64.0,
                    help="prefix-cache byte budget in MiB (LRU eviction)")
    ap.add_argument("--shared-requests", type=int, default=12,
                    help="shared-prefix workload size")
    ap.add_argument("--shared-prefix-len", type=int, default=96,
                    help="length of the shared system prompt (reusable "
                    "blocks = full --prefill-chunk multiples below this)")
    ap.add_argument("--shared-suffix-len", type=int, default=2,
                    help="unique per-request suffix after the shared prefix")
    ap.add_argument("--cold-every", type=int, default=4,
                    help="every k-th shared-prefix-workload request is a "
                    "unique cold prompt (0 = all shared)")
    ap.add_argument("--cold-prompt-len", type=int, default=48,
                    help="prompt length of the cold requests")
    ap.add_argument("--shared-new", type=int, default=4,
                    help="tokens generated per shared-prefix-workload "
                    "request (short: TTFT is the metric, not decode)")
    ap.add_argument("--draft", default=None,
                    help="draft arch name for speculative decoding (registry "
                    "name or module alias, e.g. qwen3_0p6b); adds the "
                    "schema-v4 'speculative' section — a decode-heavy "
                    "workload through a zero-tail twin target/draft pair, "
                    "spec vs non-spec, gated byte-identical (tests the "
                    "machinery and amortization ceiling, not draft quality)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="candidates per speculative verify step (1 free "
                    "target token + spec-k - 1 drafted)")
    ap.add_argument("--spec-requests", type=int, default=8,
                    help="speculative-section workload size")
    ap.add_argument("--spec-new", type=int, default=48,
                    help="tokens generated per speculative-section request "
                    "(decode-heavy: speculation is a decode optimization)")
    ap.add_argument("--mesh", default=None,
                    help="run both paths sharded over a DxM (data x model) "
                    "mesh, e.g. 1x8; axis product must equal the device "
                    "count (CPU: XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=N)")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed: the skewed request mix AND any "
                    "--scenario arrival trace are drawn from it "
                    "deterministically")
    ap.add_argument("--scenario", default=None,
                    help="also replay a named multi-tenant workload "
                    "(chat | rag | agentic | code) through the open-loop "
                    "load generator and emit the schema-v5 'workload' "
                    "section (per-tenant p50/p95/p99 TTFT+TPOT, SLO "
                    "attainment, goodput)")
    ap.add_argument("--clock", default="virtual",
                    choices=("virtual", "wall"),
                    help="scenario clock: 'virtual' (deterministic "
                    "simulated time, byte-reproducible percentiles) or "
                    "'wall' (real time on this machine)")
    ap.add_argument("--qps-scale", type=float, default=1.0,
                    help="multiply every tenant's arrival rate in the "
                    "measured scenario run")
    ap.add_argument("--saturate", action="store_true",
                    help="run the doubling+bisection saturation sweep and "
                    "report max sustainable QPS (p99 TTFT under the "
                    "scenario's loosest tenant budget); virtual clock only")
    ap.add_argument("--saturate-doublings", type=int, default=3,
                    help="rate doublings before declaring a lower bound")
    ap.add_argument("--saturate-bisects", type=int, default=3,
                    help="bisection rounds after the first failing probe")
    ap.add_argument("--step-cost-decode", type=float, default=0.01,
                    help="virtual-clock seconds per decode step")
    ap.add_argument("--step-cost-prefill", type=float, default=0.02,
                    help="virtual-clock seconds per prefill chunk")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    if args.smoke:
        cfg = cfg.with_(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                        head_dim=64, d_ff=256, vocab_size=512, loss_chunk=64)
    max_prompt = max(args.prompt_len, args.long_prompt_len)
    max_len = max_prompt + args.long_new + 1
    budget = args.admission_budget if args.admission_budget > 0 else None
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    served = quantize_for_serving(params, cfg)

    def mk_reqs():
        return make_requests(args.requests, args.short_new, args.long_new,
                             args.long_every, args.prompt_len,
                             args.long_prompt_len, args.long_prompt_every,
                             cfg.vocab_size, seed=args.seed)

    request_mix = {"requests": args.requests,
                   "short_new": args.short_new,
                   "long_new": args.long_new,
                   "long_every": args.long_every,
                   "prompt_len": args.prompt_len,
                   "long_prompt_len": args.long_prompt_len,
                   "long_prompt_every": args.long_prompt_every}
    # schema v5: + "seed", + "mode" ("paths" | "scenario").  In scenario
    # mode the "workload" key carries the per-tenant scenario report (the
    # classic request-mix params move to "request_mix"); in paths mode
    # "workload" keeps its v2+ meaning, so old consumers are untouched.
    mode = "scenario" if args.scenario else "paths"
    results = {"schema_version": 5, "arch": cfg.name, "batch": args.batch,
               "policy": args.policy, "smoke": bool(args.smoke),
               "mesh": args.mesh, "mode": mode, "seed": args.seed,
               "prefill_chunk": args.prefill_chunk,
               "admission_budget": args.admission_budget,
               ("request_mix" if mode == "scenario" else "workload"):
               request_mix}
    paths = [("generational", run_generational),
             ("continuous",
              lambda e, r: run_continuous(e, r, admission_budget=budget))]
    outs: dict[str, list[list[int]]] = {}
    for name, fn in paths:
        # fresh engine per path: identical PRNG/jit state, no cross-warming
        engine = DecodeEngine(served, cfg, batch_size=args.batch,
                              max_len=max_len, matmul_policy=args.policy,
                              prefill_chunk=args.prefill_chunk, mesh=mesh)
        # record the EFFECTIVE chunk (the engine clamps to the ring length
        # on windowed configs), not the requested flag
        results["prefill_chunk"] = engine.prefill_chunk
        results[name], outs[name] = bench(fn, engine, mk_reqs)
        r = results[name]
        print(f"[serving_bench] {name:>12}: {r['tokens']} tok in "
              f"{r['seconds']:.2f}s = {r['tok_s']:.1f} tok/s "
              f"({r['decode_steps']} decode steps, ttft mean/max "
              f"{r['ttft_s']['mean']:.3f}/{r['ttft_s']['max']:.3f}s)")

    results["speedup"] = round(
        results["continuous"]["tok_s"] / results["generational"]["tok_s"], 3)
    results["ttft_ratio"] = round(
        results["continuous"]["ttft_s"]["max"]
        / max(results["generational"]["ttft_s"]["max"], 1e-9), 3)
    print(f"[serving_bench] continuous / generational speedup: "
          f"{results['speedup']:.2f}x; worst-case ttft ratio: "
          f"{results['ttft_ratio']:.2f}")
    results["prefix"] = (bench_prefix(args, cfg, served, mesh, budget)
                         if args.prefix_cache else {"enabled": False})
    results["speculative"] = (bench_speculative(args, cfg, mesh)
                              if args.draft else {"enabled": False})
    if args.scenario:
        results["workload"], results["saturation"] = bench_scenario(
            args, cfg, served, mesh, budget)
    analysis.check_schema(results)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[serving_bench] wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
