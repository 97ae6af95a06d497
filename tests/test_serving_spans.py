"""Profiler spans of the serving loop: a tiny engine and scheduler run under
``jax.profiler.trace`` on the CPU, and the trace is read back with
``jax.profiler.ProfileData``."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.registry import get_smoke_config
from repro.models.decode import quantize_for_serving
from repro.models.model import init_params
from repro.serving.engine import SPANS, DecodeEngine, Request
from repro.serving.scheduler import ContinuousScheduler


def _host_events(directory):
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _parent(events, child, names):
    """The innermost of ``names`` that encloses ``child``."""
    enclosing = [e for e in events if e[0] in names and e is not child
                 and e[1] <= child[1] and child[2] <= e[2]]
    return enclosing[-1][0] if enclosing else None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = get_smoke_config("bitnet-b1.58-2b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)
    params = quantize_for_serving(init_params(cfg, jax.random.PRNGKey(0)),
                                  cfg)
    eng = DecodeEngine(params, cfg, batch_size=2, max_len=48,
                       matmul_policy="fixed:ref", prefill_chunk=8)

    def serve(prompts):
        sched = ContinuousScheduler(eng, admission_budget=1)
        reqs = [Request(prompt=p, max_new_tokens=3) for p in prompts]
        for r in reqs:
            sched.submit(r)
        sched.run(max_steps=64)
        return reqs

    prompts = [list(range(3, 13)), [5, 6, 7], [9] * 4]
    serve(prompts)  # compile outside the trace
    directory = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(directory)):
        reqs = serve(prompts)
    return _host_events(directory), reqs


def test_span_names_are_the_listed_ones(traced):
    events, _ = traced
    assert all(n.startswith("serve.") for n in SPANS)
    seen = {e[0] for e in events if e[0].startswith("serve.")}
    assert seen == set(SPANS)


def test_spans_nest_under_the_step(traced):
    events, _ = traced
    serve = [e for e in events if e[0].startswith("serve.")]
    want = {"serve.admit": "serve.step", "serve.prefill_chunk": "serve.step",
            "serve.decode": "serve.step", "serve.deliver": "serve.step",
            "serve.decode.launch": "serve.decode",
            "serve.decode.wait": "serve.decode"}
    for e in serve:
        assert _parent(serve, e, SPANS) == want.get(e[0]), e
    steps = [e for e in serve if e[0] == "serve.step"]
    decodes = [e for e in serve if e[0] == "serve.decode"]
    # three requests of three tokens through two slots
    assert len(decodes) >= 5 and len(steps) >= len(decodes)
    assert steps[0][3] == {"live": 0, "queued": 3, "prefilling": 0}


def test_admission_spans_carry_the_request(traced):
    events, reqs = traced
    admits = [e for e in events if e[0] == "serve.admit"]
    assert [(a[3]["rid"], a[3]["prompt_len"]) for a in admits] == \
        [(r.rid, len(r.prompt)) for r in reqs]
    chunks = [e[3]["rid"] for e in events if e[0] == "serve.prefill_chunk"]
    # the 10-token prompt takes two chunks of 8, the others one each
    assert sorted(chunks) == sorted([reqs[0].rid] * 2
                                    + [reqs[1].rid, reqs[2].rid])


def test_step_programs_carry_their_entry_point_names(traced):
    events, _ = traced
    calls = {e[0] for e in events if e[0].startswith("PjitFunction(")}
    assert {"PjitFunction(sched_step)", "PjitFunction(prefill_chunk)",
            "PjitFunction(admit_commit)"} <= calls
    assert "PjitFunction(wrapped)" not in calls
    serve = [e for e in events if e[0] in SPANS
             or e[0] == "PjitFunction(sched_step)"]
    for e in serve:
        if e[0] == "PjitFunction(sched_step)":
            assert _parent(serve, e, SPANS) == "serve.decode.launch"


class _SpecBackend:
    """Two slots that emit one or two tokens a speculative round."""

    batch_size = 2
    spec_k = 2

    def sched_start(self):
        return [0, 0]

    def sched_admit(self, state, slot, request):
        state = list(state)
        state[slot] = request.max_new_tokens
        return state

    def sched_spec_step(self, state):
        emit = np.array([min(2, max(s, 1)) for s in state])
        left = [max(s - e, 0) for s, e in zip(state, emit)]
        return (left, np.full((2, 2), 4), emit, emit,
                np.array([x > 0 for x in left]))


def test_speculative_rounds_open_decode_and_deliver(tmp_path):
    sched = ContinuousScheduler(_SpecBackend())
    for _ in range(3):
        sched.submit(Request(prompt=[1], max_new_tokens=3))
    with jax.profiler.trace(str(tmp_path)):
        sched.run(max_steps=16)
    events = [e for e in _host_events(tmp_path) if e[0] in SPANS]
    names = [e[0] for e in events]
    assert names.count("serve.decode") == sched.stats.spec_rounds
    assert names.count("serve.deliver") == sched.stats.spec_rounds
    assert names.count("serve.admit") == 3
    for e in events:
        if e[0] != "serve.step":
            assert _parent(events, e, SPANS) == "serve.step"
