"""The trace reduction, on a profiler trace recorded on a TPU v5e: two
decode steps of bitnet-b1.58-2b at 32 slots, each annotated
``bench.sched_step`` on the host."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "data"
KERNELS = Path(__file__).resolve().parents[1] / "kernels"


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "decode_b32_2steps.xplane.pb.gz").read_bytes()))
    return trace.read_events(path)


def test_ops_and_annotations_are_read(events):
    devices, host = events
    assert len(devices) == 1
    assert [h[0] for h in host] == ["bench.sched_step"] * 2
    # 2 steps x 30 layers x 7 ternary projections, one Pallas call each
    names = [n for n, _, _ in devices[0]]
    assert sum(trace.op_kind(n) == "packed_matmul" for n in names) == 420


def test_busy_is_the_union_of_op_intervals(events):
    devices, host = events
    ops = devices[0]
    # independent union: walk the ends in start order
    spans = sorted((s, s + d) for _, s, d in ops)
    total, cur_s, cur_e = 0.0, spans[0][0], spans[0][1]
    for s, e in spans[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += cur_e - cur_s
    window = (host[-1][2] - host[0][1]) / 1e9
    sm = trace.summarize(devices, host, window, 0.0, window)
    assert sm.busy_s == pytest.approx(total / 1e9, rel=1e-12)
    # nested ops (the layer loop holds the kernels) are counted once
    assert sm.busy_s < sum(d for _, _, d in ops) / 1e9
    assert 0 < sm.busy_s <= sm.window_s


def test_kernel_time_by_the_name_patterns(events):
    devices, host = events
    sm = trace.summarize(devices, host, 1.0, 0.0, 1.0)
    pats = json.loads((KERNELS / "dequant_packed.json").read_text())[
        "trace_names"]
    want = sum(d for n, _, d in devices[0]
               if n.startswith("packed_matmul")) / 1e9
    assert sm.kernel_seconds(pats) == pytest.approx(want, rel=1e-12)
    assert sm.kernel_seconds(["no_such_kernel"]) == 0.0
    # the kernels take most of the step on this chip
    assert sm.top_ops[0][0] == "packed_matmul"
    assert all(k not in trace.CONTAINERS for k, _ in sm.top_ops)
    assert len(sm.top_ops) == 10 and len(sm.idle_gaps) <= 10


def test_idle_gaps_are_labelled_by_the_host_span(events):
    devices, host = events
    sm = trace.summarize(devices, host, 1.0, 0.0, 1.0)
    labels = {g[0] for g in sm.idle_gaps}
    assert labels <= {"bench.sched_step", "host"}
    assert all(g[1] > 0 for g in sm.idle_gaps)
    assert sm.idle_gaps == sorted(sm.idle_gaps, key=lambda g: -g[1])


def test_union_helpers():
    assert trace.union_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.busy_intervals([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.op_name("%packed_matmul.69 = f32[32,2560] custom-call()") \
        == "packed_matmul.69"
    assert trace.op_kind("fusion.12.3") == "fusion"


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.summarize([], [], 1.0, 0.0, 1.0)
