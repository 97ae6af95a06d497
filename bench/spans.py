"""Where the device's idle time goes: the serving program's spans and step
programs, read from a profiler trace.

The program opens ``serve.*`` spans (``jax.profiler.TraceAnnotation``,
listed in ``repro.serving.engine.SPANS``) on the thread that drives it, and
names each compiled step ``jit_<entry point>`` (``jit_sched_step``,
``jit_prefill_chunk``, ...).  ``bench/trace.py`` reads neither: it takes
host annotations from a thread line named ``python`` only, where a
``python3`` process names it ``python3``, and keeps no ``XLA Modules``
line.  This module reads both from the same ``.xplane.pb``:

- spans: every ``serve.*`` and ``bench.*`` event on any line of
  ``/host:CPU``, with its attributes;
- modules: the ``XLA Modules`` line of the first TPU, one event per run of
  a device program, named up to its ``(``;
- busy: the union of the op intervals of the first TPU, as in
  ``bench/trace.py``.

:func:`attribute` reduces them to the step's account:

- ``idle_in_step``: share of the stretch in which a ``serve.step`` span is
  open and no device op runs; the rest of ``device_idle`` falls between
  steps (the driving loop, or no work);
- ``decode_device_ms``: mean device time of a ``jit_sched_step`` run;
- ``launches_per_step``: device programs that start inside ``serve.decode``
  spans, per span;
- the ``serve.step`` time split into ``jit_sched_step`` time inside it,
  other programs' time inside it, and idle inside it;
- idle by span: each gap between busy intervals split by the innermost
  ``serve.*`` span open over each part of it, else the ``bench.*`` one,
  else ``host``; and the longest gaps, each labelled so at its middle;
- the time outside ``serve.step`` spans, and how much of it falls between
  two steps (the rest lies before the first step or after the last).

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does and prints its result
line with these under ``program``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.trace import busy_intervals  # noqa: E402

STEP = "serve.step"
DECODE = "serve.decode"
DECODE_PROGRAM = "jit_sched_step"


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    attrs: dict = field(default_factory=dict)


@dataclass
class Events:
    spans: list[Span]
    #: (program name, start ns, duration ns), in start order
    modules: list[tuple[str, float, float]]
    #: merged (start ns, end ns) intervals in which an op ran
    busy: list[tuple[float, float]]


def from_planes(planes) -> Events:
    """The spans, programs and busy intervals of a trace's planes (those of
    ``jax.profiler.ProfileData``)."""
    spans, modules, ops = [], [], None
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats))
                          for e in line.events
                          if e.name.startswith(("serve.", "bench."))]
        elif plane.name.startswith("/device:TPU:") and ops is None:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.name.split("(", 1)[0], e.start_ns,
                                e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
    modules.sort(key=lambda m: m[1])
    return Events(spans, modules, busy_intervals(ops or []))


def read(path) -> Events:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(str(path)).planes)


def overlap_ns(busy, s: float, e: float) -> float:
    """Time of the sorted, disjoint ``busy`` intervals inside ``[s, e]``."""
    i = max(bisect.bisect_right(busy, (s,)) - 1, 0)
    total = 0.0
    while i < len(busy) and busy[i][0] < e:
        total += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return total


def innermost(spans) -> str:
    """Of spans open at one instant, in start order: the innermost
    ``serve.*`` one (the last to start), else the innermost ``bench.*`` one,
    else ``host``."""
    best = {}
    for s in spans:
        best[s.name.split(".", 1)[0]] = s.name
    return best.get("serve") or best.get("bench") or "host"


def segments(spans) -> list[tuple[float, float, str]]:
    """The spans' timeline cut where any span starts or ends, each piece
    ``(start, end, label)`` with the :func:`innermost` label of the spans
    open in it."""
    points = sorted({p for s in spans for p in (s.start_ns, s.end_ns)})
    out, open_, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i].start_ns <= a:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s.end_ns > a]
        out.append((a, b, innermost(open_)))
    return out


def gaps(busy) -> list[tuple[float, float]]:
    """The idle stretches between busy intervals."""
    return [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]


def idle_by_span(ev: Events) -> dict:
    """Seconds of idle between busy intervals, split by the
    :func:`innermost` span open in each part of each gap."""
    segs, out, j = segments(ev.spans), {}, 0
    for g0, g1 in gaps(ev.busy):
        left = g1 - g0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            t = min(g1, segs[k][1]) - max(g0, segs[k][0])
            out[segs[k][2]] = out.get(segs[k][2], 0.0) + t / 1e9
            left -= t
            k += 1
        if left > 0:
            out["host"] = out.get("host", 0.0) + left / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def longest_gaps(ev: Events, top: int = 10) -> list:
    """The ``top`` longest gaps, ``[label at the gap's middle, seconds]``."""
    segs = segments(ev.spans)
    starts = [a for a, _, _ in segs]
    out = []
    for g0, g1 in sorted(gaps(ev.busy), key=lambda g: g[0] - g[1])[:top]:
        mid = (g0 + g1) / 2
        i = bisect.bisect_right(starts, mid) - 1
        inside = i >= 0 and segs[i][1] >= mid
        out.append([segs[i][2] if inside else "host", (g1 - g0) / 1e9])
    return out


def inside(t: float, spans) -> bool:
    return any(s.start_ns <= t <= s.end_ns for s in spans)


def attribute(ev: Events, window_s: float) -> dict:
    """The step's account of one traced stretch of ``window_s`` seconds
    (see the module's docstring); times in seconds, shares in percent."""
    steps = [s for s in ev.spans if s.name == STEP]
    decodes = [s for s in ev.spans if s.name == DECODE]
    step_s = sum(s.end_ns - s.start_ns for s in steps) / 1e9
    idle_step_s = sum(s.end_ns - s.start_ns
                      - overlap_ns(ev.busy, s.start_ns, s.end_ns)
                      for s in steps) / 1e9
    runs = [m for m in ev.modules if m[0] == DECODE_PROGRAM]
    in_step = {}
    for name, s, d in ev.modules:
        t = sum(max(0.0, min(s + d, st.end_ns) - max(s, st.start_ns))
                for st in steps if st.start_ns < s + d and s < st.end_ns)
        if t:
            in_step[name] = in_step.get(name, 0.0) + t / 1e9
    per_decode = [sum(1 for _, s, _ in ev.modules
                      if d.start_ns <= s <= d.end_ns) for d in decodes]
    outside = {}
    for name, s, _ in ev.modules:
        if not inside(s, decodes):
            outside[name] = outside.get(name, 0) + 1
    between = [(b.start_ns - a.end_ns) / 1e9
               for a, b in zip(steps, steps[1:])]
    spans_ms = {}
    for sp in ev.spans:
        n, total = spans_ms.get(sp.name, (0, 0.0))
        spans_ms[sp.name] = (n + 1, total + (sp.end_ns - sp.start_ns) / 1e6)
    busy_s = sum(e - s for s, e in ev.busy) / 1e9
    sched_s = in_step.pop(DECODE_PROGRAM, 0.0)
    return {
        "window_s": window_s, "busy_s": busy_s,
        "device_idle": 100.0 * (1.0 - busy_s / window_s),
        "idle_in_step": 100.0 * idle_step_s / window_s if steps else None,
        "decode_device_ms": 1e-6 * sum(d for _, _, d in runs) / len(runs)
        if runs else None,
        "launches_per_step": sum(per_decode) / len(decodes)
        if decodes else None,
        "launches_hist": {n: per_decode.count(n)
                          for n in sorted(set(per_decode))},
        "programs_outside_decode": outside,
        "steps": len(steps), "decode_spans": len(decodes),
        "step_s": step_s, "sched_step_in_step_s": sched_s,
        "other_programs_in_step_s": in_step, "idle_in_step_s": idle_step_s,
        "idle_outside_step_s": window_s - busy_s - idle_step_s,
        "outside_steps_s": window_s - step_s,
        "between_steps_s": sum(between),
        "longest_between_steps_s": max(between, default=0.0),
        "idle_by_span_s": idle_by_span(ev),
        "idle_gaps": longest_gaps(ev),
        "span_mean_ms": {k: [n, t / n] for k, (n, t) in spans_ms.items()},
        "programs": sorted({m[0] for m in ev.modules}),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, trace

    found = {}
    reduce = trace.reduce

    def reduce_and_attribute(directory, t_start, t_stop):
        # the harness deletes its trace when the run ends: read it here
        (path,) = glob.glob(str(directory / "**" / "*.xplane.pb"),
                            recursive=True)
        found.update(attribute(read(path), t_stop - t_start))
        return reduce(directory, t_start, t_stop)

    trace.reduce = reduce_and_attribute
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    out["program"] = found
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
