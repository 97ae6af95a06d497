"""Benchmark suite: one function per paper table/figure + kernel micro-
benchmarks + the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV lines (one per benchmark) followed by
the detailed rows of each table.
"""

from __future__ import annotations

import glob
import json
import os
import time


def _roofline_summary():
    """Summarize experiments/dryrun (if the sweep has been run)."""
    pat = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*__16x16.json")
    recs = []
    for f in sorted(glob.glob(pat)):
        with open(f) as fh:
            r = json.load(fh)
        if r.get("ok"):
            recs.append(r)
    if not recs:
        return [], "dry-run not yet executed (python -m repro.launch.dryrun)"
    rows = [{"arch": r["arch"], "shape": r["shape"],
             "bottleneck": r["roofline"]["bottleneck"],
             "step_s": round(r["roofline"]["step_time_s"], 4),
             "model_flops_ratio": round(r.get("model_flops_ratio", 0), 3)}
            for r in recs]
    bn = [r["bottleneck"] for r in rows]
    derived = (f"{len(recs)} cells: {bn.count('memory')} memory-bound, "
               f"{bn.count('collective')} collective-bound, "
               f"{bn.count('compute')} compute-bound")
    return rows, derived


def main() -> None:
    from benchmarks import kernel_bench, paper_tables
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    all_rows = {}
    print("name,us_per_call,derived")
    for name, fn in paper_tables.ALL.items():
        t0 = time.perf_counter()
        rows, derived = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}")
        all_rows[name] = rows
    for name, us, derived in kernel_bench.run():
        print(f"{name},{us:.0f},{derived}")
    rows, derived = _roofline_summary()
    print(f"dryrun_roofline_summary,0,{derived}")
    all_rows["dryrun_roofline_summary"] = rows

    print("\n=== detailed rows ===")
    for name, rows in all_rows.items():
        print(f"\n-- {name} --")
        for r in rows:
            print("  " + ", ".join(f"{k}={v}" for k, v in r.items()))


if __name__ == "__main__":
    main()
