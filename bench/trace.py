"""Reduce a JAX profiler trace to the numbers the benchmark reports.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
On a TPU its device plane ``/device:TPU:<n>`` has a line ``XLA Ops`` whose
events are the HLO operations, named by their instruction text
(``%packed_matmul.69 = f32[32,2560]... custom-call(...)``); the host plane
``/host:CPU`` has a line ``python`` with the benchmark's own
``bench.*`` annotations.  Both are on one clock, in nanoseconds.

- busy time: the union of the op intervals on each device, averaged over
  the devices;
- kernel time: the summed durations of ops whose name (the instruction
  name before `` = ``, without ``%``) starts with one of a kernel's
  ``trace_names`` (``bench/kernels/<kernel>.json``);
- idle gaps: the stretches between busy intervals, each labelled with the
  ``bench.*`` host annotation that covers its middle (``host`` if none).
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

#: ops that only contain other ops; left out of the top-ops list
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%packed_matmul.69 = f32[...] ...`` -> ``packed_matmul.69``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An op's name without its instance number: ``packed_matmul``."""
    return re.sub(r"(\.\d+)+$", "", name)


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def busy_intervals(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Summary:
    """The reduced trace of one traced stretch (times in seconds; ``t_start``
    and ``t_stop`` on the window's clock)."""
    ops: list[tuple[str, float, float]]        # (op name, start s, dur s)
    busy_s: float
    window_s: float
    t_start: float
    t_stop: float
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)

    def kernel_seconds(self, patterns: list[str]) -> float:
        return sum(d for name, _, d in self.ops
                   if any(name.startswith(p) for p in patterns))

    def spans_in(self, spans, name: str) -> list:
        """The benchmark's host spans of ``name`` that ended in the stretch."""
        return [s for s in spans
                if s.name == name and self.t_start < s.t1 <= self.t_stop]


def read_events(path: Path):
    """``(device ops per device, bench host annotations)`` of one trace:
    ops as ``(name, start_ns, dur_ns)``, annotations as ``(name, start_ns,
    end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(op_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench.")]
    return devices, host


def summarize(devices, host, window_s: float, t_start: float, t_stop: float,
              top: int = 10) -> Summary:
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operations")
    busy = sum(union_ns((s, s + d) for _, s, d in ops) for ops in devices) \
        / len(devices) / 1e9
    first = devices[0]
    by_kind: dict[str, float] = {}
    for name, _, d in first:
        kind = op_kind(name)
        if kind not in CONTAINERS:
            by_kind[kind] = by_kind.get(kind, 0.0) + d / 1e9
    top_ops = sorted(([k, v] for k, v in by_kind.items()),
                     key=lambda kv: -kv[1])[:top]
    iv = busy_intervals((s, s + d) for _, s, d in first)
    gaps = []
    for (_, e0), (s1, _) in zip(iv, iv[1:]):
        mid = (e0 + s1) / 2
        label = "host"
        for name, hs, he in host:
            if hs <= mid <= he:
                label = name
        gaps.append([label, (s1 - e0) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    ops = [(n, s / 1e9, d / 1e9) for n, s, d in first]
    return Summary(ops=ops, busy_s=busy, window_s=window_s, t_start=t_start,
                   t_stop=t_stop, top_ops=top_ops, idle_gaps=gaps[:top])


def reduce(directory: Path, t_start: float, t_stop: float) -> Summary:
    """The summary of the one trace under ``directory``."""
    files = glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {directory}, found "
                         f"{len(files)}")
    devices, host = read_events(Path(files[0]))
    return summarize(devices, host, t_stop - t_start, t_start, t_stop)
