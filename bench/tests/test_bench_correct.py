"""The comparison that decides ``correct`` (``correct.judge``), the
reference it runs (the configuration's), and the roofline reader's refusal
of a kernel name table that matches nothing."""

import importlib.util
import sys
import types
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import pytest

from bench import correct
from bench import trace as trace_mod
from bench.window import Span

BENCH = Path(__file__).resolve().parents[1]
LIMITS = {"max_logit_gap": {"limit": 0.5}}


def test_without_limits_both_gaps_are_reported_and_fail():
    checks = correct.judge([0.0, 0.1, 0.2], 0, {})
    assert checks["max_logit_gap"] == {"value": 0.2, "limit": None}
    assert checks["mean_logit_gap"]["value"] == pytest.approx(0.1)
    assert checks["mean_logit_gap"]["limit"] is None
    assert not correct.passed(checks)


def test_only_the_limited_gaps_are_compared():
    checks = correct.judge([0.0, 0.4], 0, LIMITS)
    assert set(checks) == {"wrong_lengths", "max_logit_gap"}
    assert correct.passed(checks)


@pytest.mark.parametrize("gaps,wrong,ok", [
    ([0.0, 0.5], 0, True),      # at the limit
    ([0.0, 0.51], 0, False),    # a token past it
    ([0.0, 0.1], 1, False),     # a request served the wrong count
    ([], 0, False),             # nothing was checked
])
def test_each_check_holds_its_limit(gaps, wrong, ok):
    assert correct.passed(correct.judge(gaps, wrong, LIMITS)) is ok


def _stub_arch(monkeypatch, gap):
    """An architecture module ``bench.arch.stub`` whose reference reads
    ``gap`` at every position."""
    class Reference:
        def __init__(self, model, seed):
            pass

        def score(self, tokens, rows, targets, act_bits):
            return np.full(len(rows), gap), np.zeros(len(rows), np.int32)

    mod = types.ModuleType("bench.arch.stub")
    mod.Reference = Reference
    monkeypatch.setitem(sys.modules, "bench.arch.stub", mod)


def _finished(rid, prompt, out):
    req = SimpleNamespace(out=out)
    return SimpleNamespace(rid=rid, prompt=prompt, request=req, done=1.0,
                           n_out=len(out), budget=len(out))


@pytest.mark.parametrize("gap,ok", [(0.0, True), (5.0, False)])
def test_verify_runs_the_reference_the_configuration_names(monkeypatch, gap,
                                                            ok):
    _stub_arch(monkeypatch, gap)
    win = SimpleNamespace(records=[_finished(0, [5, 6, 7], [8, 9]),
                                   _finished(1, [4], [3, 2, 1])])
    model = {"architecture": "stub", "activations": "bfloat16"}
    checks = correct.verify(win, model, 7, LIMITS)
    assert checks["max_logit_gap"]["value"] == gap
    assert correct.passed(checks) is ok


def _roofline():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_ternary_roofline", BENCH / "metrics" /
        "ternary_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(kernels, op="packed_matmul.3"):
    summary = trace_mod.Summary(ops=[(op, 0.1, 0.05)], busy_s=0.05,
                                window_s=1.0, t_start=0.0, t_stop=1.0)
    model = {"architecture": "bitnet", "hidden_size": 256, "intermediate_size": 512,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 64,
             "activations": "bfloat16"}
    win = SimpleNamespace(spans=[Span("sched_step", 0.1, 0.2)], slots=8)
    return SimpleNamespace(trace=summary, kernels=kernels, win=win,
                           cell=SimpleNamespace(model=model),
                           device_kind="TPU v5 lite", prefill_chunk=32)


def test_the_roofline_reads_a_matched_kernel():
    v = _roofline().read(_ctx({"q": "dequant_packed"}))
    assert 0 < v < 100


def test_a_kernel_table_that_matches_no_op_is_an_error():
    with pytest.raises(ValueError):
        _roofline().read(_ctx({"q": "dequant_packed"}, op="fusion.7"))


def test_a_kernel_without_a_name_file_is_an_error():
    with pytest.raises(FileNotFoundError):
        _roofline().read(_ctx({"q": "no_such_kernel"}))
