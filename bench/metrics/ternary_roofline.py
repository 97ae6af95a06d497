"""Kernels: least time of the ternary projections the traced stretch ran,
over the device time of the ops that ran them.

The work is each step's projections at the model's shapes (decode steps at
M = slots, prefill chunks at M = chunk), counted by the configuration's
architecture module (``least_seconds`` of ``bench/arch/<architecture>.py``)
at 1.6 bits per weight; the least time of a projection is the larger of its
operations over the peak and its bytes over HBM bandwidth.  The ops are
matched by the name patterns of ``bench/kernels/<kernel>.json`` for each
kernel dispatch chose (``DecodeEngine.kernel_choices``).  A chosen kernel
with no name file, or whose patterns match no op of a stretch that ran
steps, is an error: the table is wrong, and the metric must not drop out
unseen."""

import json
from pathlib import Path

from bench import arch, work

KERNELS = Path(__file__).resolve().parents[1] / "kernels"


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    patterns = []
    for name in sorted(set(ctx.kernels.values())):
        f = KERNELS / f"{name}.json"
        if not f.exists():
            raise FileNotFoundError(f"kernel {name!r} has no name file {f}")
        patterns += json.loads(f.read_text())["trace_names"]
    steps = len(t.spans_in(ctx.win.spans, "sched_step"))
    chunks = len(t.spans_in(ctx.win.spans, "sched_admit_step"))
    if not steps + chunks:
        return None
    device_s = t.kernel_seconds(patterns)
    if not device_s:
        raise ValueError(f"no op of the trace matches {patterns}, though "
                         f"{steps} steps and {chunks} chunks ran")
    model = ctx.cell.model
    least_seconds = arch.load(model).least_seconds
    int8 = model["activations"].startswith("int8")
    pk = work.peaks(ctx.device_kind)
    peak = pk["int8_ops"] if int8 else pk["bf16_flops"]
    act_bytes = 1 if int8 else 2
    least = steps * least_seconds(ctx.win.slots, model, act_bytes, peak,
                                  pk["hbm_bytes_per_s"]) \
        + chunks * least_seconds(ctx.prefill_chunk, model, act_bytes, peak,
                                 pk["hbm_bytes_per_s"])
    return 100.0 * least / device_s
