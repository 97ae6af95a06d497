#!/usr/bin/env python3
"""Serve bitnet-b1.58-2b at its published widths on one TPU chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 1x4 tensor-parallel mesh vs 1 device

The quickest proof that the serving path — ``load_served`` (random weights
from ``--seed``, quantized to the 1.6 b/w packed artifact) → ``DecodeEngine``
→ ``ContinuousScheduler`` → kernel dispatch under ``policy="auto"`` — starts
on the chip and produces the right numbers.  It calls the same functions as
``python -m repro.launch.serve``, in one process that holds the chip.

One chip, for activation dtype ``none`` (bf16) and then ``int8``:
  * the kernel dispatch chose for each projection shape;
  * each chosen kernel's max |error| against the ``ref`` kernel at that
    shape, on the chip, within its tolerance (int8: exact; bf16: 2^-10 of
    the largest |ref| output — products are exact, only f32 summation order
    differs);
  * last-position prefill logits of one prompt through the served model vs
    the same model pinned to ``fixed:ref`` (argmax must agree unless the
    reference's top-2 gap is within the measured difference);
  * 8 requests (prompts of 16-128 tokens, 32 new tokens each) at batch 4 and
    ``max_len`` 2048, served cold and then warm (the streams must agree):
    tokens served, compile seconds (XLA backend compiles, persistent-cache
    reads included), wall seconds, peak device memory.

``--four-chips`` serves the same requests at ``act_dtype`` none on
``make_serving_mesh("1x4")`` and on one device of the same process, both
with canonical (bf16-argmax) greedy selection.  The token streams must match,
or the first divergence is reported with the single-device logit gap between
the two tokens; a gap above 10% of the largest |logit| fails.

Exits non-zero, printing no result, when JAX finds no TPU or a phase fails.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.encoding import pack_base3  # noqa: E402
from repro.kernels.dispatch import TernaryWeight, get_kernel  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (describe_devices, load_served,  # noqa: E402
                                serve_requests)
from repro.models.decode import packed_bits_per_weight, prefill  # noqa: E402
from repro.serving.engine import (DecodeEngine, Request,  # noqa: E402
                                  SamplerConfig)

ARCH = "bitnet-b1.58-2b"
BATCH, MAX_LEN, PREFILL_CHUNK = 4, 2048, 32
N_REQUESTS, NEW_TOKENS, PROMPT_LEN = 8, 32, (16, 128)
#: kernel-vs-ref tolerance, as a fraction of the largest |ref| output
KERNEL_RTOL = {"int8": 0.0, "bfloat16": 2.0 ** -10}
#: a greedy divergence whose logit gap exceeds this share of max |logit|
#: is a wrong result, not a near-tie
DIVERGENCE_RTOL = 0.1

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), and persistent-cache hits, since construction."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def say(*parts):
    print("[chip_smoke]", *parts, flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def make_requests(vocab: int, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    return [Request(prompt=rng.integers(2, vocab, size=int(n)).tolist(),
                    max_new_tokens=NEW_TOKENS) for n in lens]


def act_name(cfg) -> str:
    return "int8" if cfg.act_dtype == "int8" else cfg.dtype


def check_kernels(choices: dict, act: str, seed: int) -> None:
    """Each chosen kernel vs ``ref`` at its real shape, on the device."""
    bad = []
    shapes = sorted({(m, k, n, name)
                     for (_, _, m, k, n), name in choices.items()})
    for i, (m, k, n, name) in enumerate(shapes):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed + i))
        if act == "int8":
            x = jax.random.randint(kx, (m, k), -127, 128, jnp.int32)
            x = x.astype(jnp.int8)
        else:
            x = jax.random.normal(kx, (m, k), jnp.dtype(act))
        trits = jax.random.randint(kw, (n, k), -1, 2, jnp.int32)
        packed = pack_base3(trits.astype(jnp.int8))

        def run(spec):
            return jax.jit(lambda xx, pk: spec.run(
                xx, TernaryWeight.from_packed(pk, 1.0, k), 3, None))(
                    x, packed)

        y = np.asarray(run(get_kernel(name)), np.float64)
        ref = np.asarray(run(get_kernel("ref")), np.float64)
        err = float(np.abs(y - ref).max())
        tol = KERNEL_RTOL[act] * float(np.abs(ref).max())
        ok = err <= tol
        say(f"kernel {name} M{m} K{k} N{n} {act}: max|err| vs ref {err!r} "
            f"(tolerance {tol!r}) {'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append((name, m, k, n))
    if bad:
        raise AssertionError(f"kernels outside tolerance: {bad}")


def check_logits(served, cfg, prompt: list[int]) -> None:
    """Served-model prefill logits vs the same model on ``fixed:ref``."""
    toks = jnp.asarray([prompt], jnp.int32)

    def logits(c):
        fn = jax.jit(lambda p, t: prefill(p, c, {"tokens": t},
                                          s_max=len(prompt))[1])
        return np.asarray(fn(served, toks), np.float64)[0]

    got = logits(cfg)
    ref = logits(cfg.with_(matmul_policy="fixed:ref"))
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite prefill logits")
    diff = float(np.abs(got - ref).max())
    top2 = np.sort(ref)[-2:]
    gap = float(top2[1] - top2[0])
    agree = int(got.argmax()) == int(ref.argmax())
    say(f"prefill logits vs fixed:ref ({act_name(cfg)}): max|diff| {diff!r}, "
        f"max|ref| {float(np.abs(ref).max())!r}, argmax agree {agree}, "
        f"ref top-2 gap {gap!r}")
    if not agree and gap > diff:
        raise AssertionError("served argmax differs from fixed:ref beyond "
                             "the measured logit difference")


def serve_phase(engine, cfg, seed: int, clock, label: str) -> list[Request]:
    """Serve the seeded requests twice: the first pass compiles, the second
    runs warm and must emit the same streams.  Returns the first pass."""
    passes = []
    for warm in (False, True):
        reqs = make_requests(cfg.vocab_size, seed)
        c0, n0, h0 = clock.snapshot()
        t0 = time.perf_counter()
        steps, _ = serve_requests(engine, reqs)
        wall = time.perf_counter() - t0
        c1, n1, h1 = clock.snapshot()
        toks = sum(len(r.out) for r in reqs)
        say(f"served {label} {'warm' if warm else 'cold'}: {len(reqs)} "
            f"requests, {toks} tokens in {steps} decode steps, {wall!r} s "
            f"wall (not a benchmark); compile {c1 - c0!r} s over "
            f"{n1 - n0} compiles, {h1 - h0} persistent-cache hits; peak "
            f"device bytes {peak_bytes()}")
        for r in reqs:
            if len(r.out) != r.max_new_tokens or \
                    not all(0 <= t < cfg.vocab_size for t in r.out):
                raise AssertionError(f"bad stream for request {r.rid}: "
                                     f"{r.out}")
        passes.append(reqs)
    if [r.out for r in passes[0]] != [r.out for r in passes[1]]:
        raise AssertionError(f"{label}: warm pass streams differ from cold")
    return passes[0]


def one_chip(args, clock) -> None:
    base = get_config(ARCH)
    served = load_served(base, seed=args.seed)
    say(f"{base.name} at full width: {base.n_layers} layers, d_model "
        f"{base.d_model}, d_ff {base.d_ff}, vocab {base.vocab_size}; packed "
        f"{packed_bits_per_weight(served):.3f} b/w")
    for act_dtype in ("none", "int8"):
        cfg = base if act_dtype == "none" else base.with_(act_dtype="int8")
        engine = DecodeEngine(served, cfg, batch_size=BATCH, max_len=MAX_LEN,
                              prefill_chunk=PREFILL_CHUNK)
        choices = engine.kernel_choices()
        for (entry, role, m, k, n), name in choices.items():
            say(f"dispatch {act_name(cfg)} {entry} {role} M{m} K{k} N{n}: "
                f"{name}")
        check_kernels(choices, act_name(cfg), args.seed)
        reqs = serve_phase(engine, cfg, args.seed, clock, act_name(cfg))
        check_logits(served, cfg, reqs[0].prompt[:PREFILL_CHUNK])
        del engine


def first_divergence(served, cfg, reqs, base_outs, mesh_outs) -> None:
    for r, a, b in zip(reqs, base_outs, mesh_outs):
        if a == b:
            continue
        t = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        toks = jnp.asarray([r.prompt + a[:t]], jnp.int32)
        fn = jax.jit(lambda p, tk: prefill(p, cfg, {"tokens": tk},
                                           s_max=toks.shape[1])[1])
        logits = np.asarray(fn(served, toks), np.float64)[0]
        gap = float(logits[a[t]] - logits[b[t]])
        scale = float(np.abs(logits).max())
        say(f"first divergence: request {r.rid} token {t}: one device "
            f"{a[t]}, mesh {b[t]}; one-device logit gap {gap!r} "
            f"(max|logit| {scale!r})")
        if abs(gap) > DIVERGENCE_RTOL * scale:
            raise AssertionError("mesh stream diverges beyond a near-tie")
        return


def four_chips(args, clock) -> None:
    from repro.launch.mesh import make_serving_mesh

    if len(jax.devices()) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX found "
                         f"{len(jax.devices())}")
    cfg = get_config(ARCH)
    served = load_served(cfg, seed=args.seed)
    greedy = SamplerConfig(canonical_greedy=True)
    mesh_engine = DecodeEngine(served, cfg, batch_size=BATCH, max_len=MAX_LEN,
                               prefill_chunk=PREFILL_CHUNK, sampler=greedy,
                               mesh=make_serving_mesh("1x4"))
    choices = mesh_engine.kernel_choices()
    for (entry, role, m, k, n), name in choices.items():
        say(f"dispatch mesh 1x4 {entry} {role} M{m} K{k} N{n}: {name}")
    # the comparison runs the kernel the mesh path runs, on one device
    policy = ("fixed:" + next(iter(set(choices.values())))
              if len(set(choices.values())) == 1 else None)
    one_engine = DecodeEngine(served, cfg, batch_size=BATCH, max_len=MAX_LEN,
                              prefill_chunk=PREFILL_CHUNK, sampler=greedy,
                              matmul_policy=policy)
    say(f"one-device comparison policy: {policy or 'auto'}")
    one_reqs = serve_phase(one_engine, cfg, args.seed, clock, "one device")
    mesh_reqs = serve_phase(mesh_engine, cfg, args.seed, clock, "mesh 1x4")
    base_outs = [r.out for r in one_reqs]
    mesh_outs = [r.out for r in mesh_reqs]
    same = sum(a == b for a, b in zip(base_outs, mesh_outs))
    say(f"mesh 1x4 vs one device: {same}/{len(base_outs)} greedy streams "
        f"identical")
    if same < len(base_outs):
        ref_cfg = cfg if policy is None else cfg.with_(matmul_policy=policy)
        first_divergence(served, ref_cfg, one_reqs, base_outs, mesh_outs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 1x4 tensor-parallel mesh and compare "
                    "with one device (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    device = describe_devices()
    say(f"device {device}")
    if device["platform"] != "tpu":
        print(f"[chip_smoke] no TPU: JAX runs on {device['platform']}",
              file=sys.stderr)
        return 1
    say(f"compile cache {cache_dir}")
    clock = CompileClock()
    (four_chips if args.four_chips else one_chip)(args, clock)
    seconds, compiles, hits = clock.snapshot()
    say(f"total: compile {seconds!r} s over {compiles} compiles, {hits} "
        f"persistent-cache hits; peak device bytes {peak_bytes()}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
