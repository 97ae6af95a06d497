"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/limits.py --workload bitnet2b-int8.chat \
        --seeds 101 102 103 --seconds 51

In one process, for each seed of ``--seeds`` it makes the served model,
drives the cell's traffic for its warm-up and a window of ``--seconds``,
frees the program and reads the widest logit gap of the sampled served
tokens under the configuration's reference (``bench/correct.py``, with the
``Reference`` of ``bench/arch/<architecture>.py``): the sound program's
readings, whose largest is the limit's lower reading.

The control is the reference's precision one step below the one the
configuration states, and its smallest reading is the upper one:

- bf16 activations: the program's own int8 activation path (W1.58A8),
  served on ``--control-seeds`` and read against the bf16 reference;
- int8 activations: the reference with int4 activations, at each position
  of the sampled prompts and served tokens of the sound runs; the token it
  puts first is read against the int8 reference.

Each reading is one JSON line on standard output.  Where the cell already
has its limits (``bench/limits/<cell>.json``), each line also carries the
checks that the benchmark's own comparison (``correct.judge``) makes of
those gaps, and whether they pass: a control has to come out not correct.
The benchmark's own runs do not run this.
"""

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def gaps_of(win, model: dict, seed: int) -> list[float]:
    import jax

    from bench import arch, correct

    with jax.default_matmul_precision("highest"):
        ref = arch.load(model).Reference(model, seed)
        return correct.served_gaps(ref, correct.sample(win.records, seed),
                                   8 if model["activations"].startswith(
                                       "int8") else None)


def served_window(cfg, seed: int, cell, seconds: float):
    from bench import harness, window

    engine = harness.build(cfg, seed, cell.mix)
    win = window.drive(engine, cell.mix, seed, cfg.vocab_size,
                       seconds=seconds)
    del engine
    gc.collect()
    return win


def int4_control(win, model: dict, seed: int) -> list[float]:
    """The int8-reference gap of each token that int4 activations put first,
    over the sample of ``win``."""
    import jax

    from bench import arch, correct

    gaps = []
    with jax.default_matmul_precision("highest"):
        ref = arch.load(model).Reference(model, seed)
        for r in correct.sample(win.records, seed):
            toks = r.prompt + r.request.out[:-1]
            p = len(r.prompt)
            rows = list(range(p - 1, p - 1 + len(r.request.out)))
            _, best4 = ref.score(toks, rows, r.request.out, 4)
            gap, _ = ref.score(toks, rows, best4.tolist(), 8)
            gaps += [float(g) for g in gap]
    return gaps


def reading(kind: str, seed: int, gaps: list[float], limits: dict,
            wrong: int = 0) -> dict:
    from bench import correct

    row = {"kind": kind, "seed": seed, "max_logit_gap": max(gaps),
           "mean_logit_gap": sum(gaps) / len(gaps),
           "checked_tokens": len(gaps), "wrong_lengths": wrong}
    if limits:
        checks = correct.judge(gaps, wrong, limits)
        row.update(correct=correct.passed(checks), checks=checks)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.spec["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = harness.program_config(cell.model)
    int8 = cell.model["activations"].startswith("int8")
    for seed in args.seeds:
        win = served_window(cfg, seed, cell, args.seconds)
        wrong = sum(1 for r in win.records if r.done is not None
                    and len(r.request.out) != r.budget)
        print(json.dumps(reading("program", seed, gaps_of(win, cell.model,
                                                          seed),
                                 cell.limits, wrong)), flush=True)
        if int8:
            print(json.dumps(reading("control_int4_reference", seed,
                                     int4_control(win, cell.model, seed),
                                     cell.limits)), flush=True)
    if not int8:
        ccfg = cfg.with_(act_dtype="int8")
        for seed in args.control_seeds:
            win = served_window(ccfg, seed, cell, args.seconds)
            print(json.dumps(reading("control_int8_program", seed,
                                     gaps_of(win, cell.model, seed),
                                     cell.limits)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
