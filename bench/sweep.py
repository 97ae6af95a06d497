"""Knee sweep of an open-loop mix, on the chip, on the wall clock.

    python3 bench/sweep.py --config bitnet2b-bf16 --traffic chat \
        --slots 8 16 --rates 0.2 0.3 0.4 --seconds 30 --warmup 15 --seed 1

One process per configuration: the weights are made once, then for each
slot count an engine is built and every rate is run through a fresh
scheduler (warm-up, then a window).  For each rate it prints one JSON line:
the share of requests due in the window that met both latency limits of the
mix (``limits``: TTFT and TPOT; a request with no token by the close
counts with its wait so far), the p90 tails, and the queue at the window's
open and close.  The knee is the highest rate whose share is at least
``limits.attainment`` with no growing queue.  The benchmark's own runs do
not run this; the rate it finds is written into the mix's file by hand.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def attainment(win, limits: dict) -> float:
    due = win.due_in_window()
    met = 0
    for r in due:
        times = [t for t in r.times if t < win.close]
        ttft = (times[0] if times else win.close) - r.due
        tpot = (times[-1] - times[0]) / (len(times) - 1) \
            if len(times) > 1 else 0.0
        met += ttft <= limits["ttft_s"] and tpot <= limits["tpot_s"]
    return met / len(due) if due else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warmup", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import gc

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import load_served

    from bench import harness, window
    from bench.stats import percentile

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    model = json.loads((ROOT / entry["file"]).read_text())
    base = json.loads((ROOT / "bench" / "traffic" /
                       f"{args.traffic}.json").read_text())
    harness.check_devices(1)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = harness.program_config(model)
    served = load_served(cfg, seed=args.seed)
    for slots in args.slots:
        mix = dict(base, slots=slots)
        engine = harness.build(cfg, args.seed, mix, served=served)
        low = 0
        for rate in args.rates:
            m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
            win = window.drive(engine, m, args.seed, cfg.vocab_size,
                               seconds=args.seconds, warmup_s=args.warmup)
            ttfts, tpots = win.ttfts(), win.tpots()
            row = {"config": args.config, "slots": slots, "rate": rate,
                   "due": len(win.due_in_window()),
                   "attainment": attainment(win, mix["limits"]),
                   "ttft_p90_s": percentile(ttfts, 90) if ttfts else None,
                   "tpot_p90_s": percentile(tpots, 90) if tpots else None,
                   "served_tok_s": win.served_tok_s(),
                   "queued_open": win.queued_at_open,
                   "queued_close": win.queued_at_close}
            print(json.dumps(row), flush=True)
            low = low + 1 if row["attainment"] < 0.5 else 0
            if low >= 2:
                break
        del engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
