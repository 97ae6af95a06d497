"""The reduction of program spans and step programs (``bench/spans.py``),
on hand-built trace planes whose host line is named ``python3``."""

from types import SimpleNamespace as NS

import pytest

from bench import spans
from bench.spans import Events, Span


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def planes(host_lines, modules=(), ops=()):
    return [
        NS(name="/host:CPU", lines=[NS(name=n, events=e)
                                    for n, e in host_lines]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=list(modules)),
            NS(name="XLA Ops", events=list(ops))]),
        # a second chip's programs are not read
        NS(name="/device:TPU:1", lines=[
            NS(name="XLA Modules", events=[ev("jit_other(1)", 0, 5)])]),
    ]


def test_spans_and_attributes_are_read_from_every_host_line():
    got = spans.from_planes(planes([
        ("python3", [ev("serve.step", 0, 100, live=3, queued=0),
                     ev("$scheduler.py:363 step", 1, 98),
                     ev("bench.sched_step", 10, 80),
                     ev("serve.admit", 2, 5, rid=7, prompt_len=40)]),
        ("main/287", [ev("PJRT_LoadedExecutable_Execute", 12, 3)]),
        ("worker", [ev("serve.prefill_chunk", 200, 10, rid=7)]),
    ]))
    assert [(s.name, s.start_ns, s.end_ns) for s in got.spans] == [
        ("serve.step", 0, 100), ("serve.admit", 2, 7),
        ("bench.sched_step", 10, 90), ("serve.prefill_chunk", 200, 210)]
    assert got.spans[0].attrs == {"live": 3, "queued": 0}
    assert got.spans[1].attrs == {"rid": 7, "prompt_len": 40}
    assert got.spans[3].attrs == {"rid": 7}


def test_modules_are_read_by_name_on_the_first_chip():
    got = spans.from_planes(planes(
        [], modules=[ev("jit_sched_step(4642868976104185547)", 50, 30),
                     ev("jit__threefry_split(1193)", 10, 2)],
        ops=[ev("%a = f32[2] add()", 10, 2), ev("%b = f32[2] mul()", 11, 3),
             ev("%c = f32[2] dot()", 50, 30)]))
    assert got.modules == [("jit__threefry_split", 10, 2),
                           ("jit_sched_step", 50, 30)]
    assert got.busy == [(10, 14), (50, 80)]


def test_gap_labels_prefer_the_innermost_serve_span():
    nested = [Span("serve.step", 0, 100), Span("serve.decode", 10, 90),
              Span("bench.sched_step", 12, 88),
              Span("serve.decode.wait", 60, 88),
              Span("bench.sched_admit_step", 150, 190)]
    assert spans.innermost(nested[:3]) == "serve.decode"
    assert spans.innermost(nested) == "serve.decode.wait"
    assert spans.innermost(nested[2:3]) == "bench.sched_step"
    assert spans.innermost([]) == "host"
    busy = [(0, 5), (15, 20), (70, 80), (95, 140), (160, 170), (200, 210)]
    ev = Events(nested, [], busy)
    # gaps 5-15, 20-70, 80-95, 140-160 and 170-200, split where a span
    # opens or closes: serve.step 5-10 and 90-95; serve.decode 10-15,
    # 20-60 and 88-90 (over bench.sched_step); serve.decode.wait 60-70 and
    # 80-88; host 140-150 and 190-200; bench.sched_admit_step 150-160 and
    # 170-190
    got = spans.idle_by_span(ev)
    assert got == pytest.approx({
        "serve.decode": 47e-9, "bench.sched_admit_step": 30e-9,
        "host": 20e-9, "serve.decode.wait": 18e-9, "serve.step": 10e-9})
    assert list(got)[0] == "serve.decode"
    assert sum(got.values()) == pytest.approx(125e-9)
    assert spans.longest_gaps(ev, top=4) == [
        ["serve.decode", 50e-9], ["bench.sched_admit_step", 30e-9],
        ["bench.sched_admit_step", 20e-9], ["serve.decode.wait", 15e-9]]
    lone = Events([], [], [(0, 1), (3, 4)])
    assert spans.idle_by_span(lone) == pytest.approx({"host": 2e-9})
    assert spans.longest_gaps(lone) == [["host", 2e-9]]


def test_idle_in_step_counts_idle_inside_nested_spans_once():
    # two steps of 100 ns, each with nested spans; ops busy 60 ns of the
    # first and 30 ns of the second (one op straddles its start); idle
    # between the steps does not count
    steps = [Span("serve.step", 0, 100), Span("serve.decode", 20, 100),
             Span("serve.decode.wait", 50, 100),
             Span("serve.step", 300, 400), Span("serve.deliver", 390, 400)]
    busy = [(10, 40), (60, 90), (150, 250), (290, 310), (350, 370)]
    got = spans.attribute(Events(steps, [], busy), window_s=1e-6)
    assert got["idle_in_step_s"] == pytest.approx((40 + 70) * 1e-9)
    assert got["idle_in_step"] == pytest.approx(11.0)
    assert got["busy_s"] == pytest.approx(200e-9)
    assert got["device_idle"] == pytest.approx(80.0)
    assert got["idle_outside_step_s"] == pytest.approx((1000 - 200 - 110)
                                                       * 1e-9)
    assert got["steps"] == 2
    assert got["outside_steps_s"] == pytest.approx(800e-9)
    assert got["between_steps_s"] == pytest.approx(200e-9)
    assert got["longest_between_steps_s"] == pytest.approx(200e-9)


def test_decode_device_ms_is_the_sched_step_program_alone():
    mods = [("jit__threefry_split", 0, 3_000), ("jit__unstack", 5_000, 800),
            ("jit_sched_step", 10_000, 24_000_000),
            ("jit_prefill_chunk", 30_000_000, 90_000_000),
            ("jit_sched_step", 130_000_000, 26_000_000)]
    got = spans.attribute(Events([], mods, []), window_s=1.0)
    assert got["decode_device_ms"] == pytest.approx(25.0)
    assert got["programs"] == ["jit__threefry_split", "jit__unstack",
                               "jit_prefill_chunk", "jit_sched_step"]
    assert got["idle_in_step"] is None and got["launches_per_step"] is None
    none = spans.attribute(Events([], [("jit_wrapped", 0, 5)], []), 1.0)
    assert none["decode_device_ms"] is None


def test_launches_per_step_counts_programs_started_in_decode_spans():
    decode = [Span("serve.step", 0, 1_000), Span("serve.decode", 100, 900),
              Span("serve.step", 2_000, 3_000),
              Span("serve.decode", 2_100, 2_900),
              # an admission-only step: its chunk is not a decode launch
              Span("serve.step", 4_000, 5_000),
              Span("serve.prefill_chunk", 4_100, 4_900)]
    mods = [("jit__threefry_split", 110, 5), ("jit__unstack", 130, 2),
            ("jit_sched_step", 200, 600),
            ("jit__threefry_split", 2_110, 5), ("jit__unstack", 2_130, 2),
            ("jit_sched_step", 2_200, 600),
            ("jit_prefill_chunk", 4_200, 600)]
    got = spans.attribute(Events(decode, mods, []), window_s=1.0)
    assert got["launches_per_step"] == pytest.approx(3.0)
    assert got["launches_hist"] == {3: 2}
    assert got["programs_outside_decode"] == {"jit_prefill_chunk": 1}
    assert got["decode_spans"] == 2


def test_the_step_account_adds_up():
    """``serve.step`` time is the decode program's time in it, the other
    programs' time in it, and the idle in it."""
    st = [Span("serve.step", 0, 1_000), Span("serve.decode", 100, 1_000),
          Span("serve.step", 2_000, 3_000)]
    mods = [("jit__threefry_split", 110, 10), ("jit_sched_step", 200, 700),
            ("jit_prefill_chunk", 2_100, 500),
            # a program running past a step's end counts inside it only
            ("jit_admit_commit", 2_900, 200)]
    busy = [(m[1], m[1] + m[2]) for m in mods]
    got = spans.attribute(Events(st, mods, busy), window_s=4e-6)
    assert got["sched_step_in_step_s"] == pytest.approx(700e-9)
    assert got["other_programs_in_step_s"] == pytest.approx(
        {"jit__threefry_split": 10e-9, "jit_prefill_chunk": 500e-9,
         "jit_admit_commit": 100e-9})
    total = got["sched_step_in_step_s"] + got["idle_in_step_s"] + sum(
        got["other_programs_in_step_s"].values())
    assert total == pytest.approx(got["step_s"]) == pytest.approx(2e-6)


def test_overlap_of_busy_intervals_with_a_span():
    busy = [(0, 10), (20, 30), (40, 50)]
    assert spans.overlap_ns(busy, 5, 45) == 5 + 10 + 5
    assert spans.overlap_ns(busy, 10, 20) == 0
    assert spans.overlap_ns(busy, 60, 70) == 0
    assert spans.overlap_ns([], 0, 1) == 0
