"""Continuous-batching scheduler: property-tested invariants (fake backend),
the continuous-vs-single-request differential oracle (real engine), the
generational run() overflow guard, and the tier-2 soak test (`slow` marker,
run by the scheduled CI job — tier-1 skips it via pytest.ini addopts)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_smoke_config
from repro.models.decode import decode_step, prefill, quantize_for_serving
from repro.models.model import init_params
from repro.serving.engine import DecodeEngine, Request
from repro.serving.scheduler import ContinuousScheduler


# ---------------------------------------------------------------------------
# fake backend: scheduler invariants without a model
# ---------------------------------------------------------------------------


class FakeBackend:
    """Deterministic ScheduleBackend: each request carries a scripted token
    stream (``req._script``); slot ``b`` replays its request's script one
    token per step.  Asserts the scheduler never refills a live slot."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self.admitted: list[Request] = []

    def sched_start(self):
        return [None] * self.batch_size  # slot → {"req", "emitted"} | None

    def sched_admit(self, state, slot, request):
        assert state[slot] is None, f"refill clobbered live slot {slot}"
        self.admitted.append(request)
        state = list(state)
        state[slot] = {"req": request, "emitted": 0}
        return state

    def sched_step(self, state):
        B = self.batch_size
        tokens = np.full(B, -1, np.int64)
        alive = np.zeros(B, bool)
        state = list(state)
        for b, s in enumerate(state):
            if s is None:
                continue
            req, t = s["req"], s["emitted"]
            tok = req._script[t]
            s["emitted"] = t + 1
            tokens[b] = tok
            stopped = req.stop_token is not None and tok == req.stop_token
            if stopped or s["emitted"] >= req.max_new_tokens:
                state[b] = None  # backend-side: slot is dead now
            else:
                alive[b] = True
        return state, tokens, alive


class FakeSpecBackend:
    """Deterministic ScheduleBackend speaking the speculative
    accept/rollback protocol: per round, slot ``b`` accepts a ragged
    1..spec_k-token window of its request's script (``accept(round, slot)``
    decides how many), then applies the engine's stop/budget masking.  The
    candidate rows are padded past the script with ``-7`` poison — a
    scheduler that reads past ``n_emit`` emits poison and fails the stream
    equality checks."""

    #: sched_spec_step accepts the optional per-slot window argument
    spec_window_aware = True

    def __init__(self, batch_size: int, spec_k: int = 3, accept=None):
        self.batch_size = batch_size
        self.spec_k = spec_k
        self.accept = accept or (lambda rnd, b: 1 + (rnd + b) % spec_k)
        self.admitted: list[Request] = []
        self.rounds = 0
        self.drafted = 0
        self.accepted = 0
        #: rid → list of draft windows the scheduler asked for (dynamic
        #: spec_k assertions)
        self.windows_seen: dict[int, list[int]] = {}

    def sched_start(self):
        return [None] * self.batch_size

    def sched_admit(self, state, slot, request):
        assert state[slot] is None, f"refill clobbered live slot {slot}"
        self.admitted.append(request)
        state = list(state)
        state[slot] = {"req": request, "emitted": 0}
        return state

    def sched_step(self, state):
        raise AssertionError("speculative backend: the scheduler must route "
                             "through sched_spec_step, not sched_step")

    def sched_spec_step(self, state, window=None):
        B, K = self.batch_size, self.spec_k
        win = [K] * B if window is None else [int(w) for w in window]
        assert all(2 <= w <= K for w in win), win
        tokens = np.full((B, K), -7, np.int64)  # poison past the window
        n_acc = np.zeros(B, np.int64)
        n_emit = np.zeros(B, np.int64)
        alive = np.zeros(B, bool)
        state = list(state)
        for b, s in enumerate(state):
            if s is None:
                continue
            req, t = s["req"], s["emitted"]
            self.windows_seen.setdefault(req.rid, []).append(win[b])
            remaining = req.max_new_tokens - t
            window_toks = req._script[t:t + K]
            tokens[b, :len(window_toks)] = window_toks
            # the draft window caps the accepted prefix (the engine rejects
            # everything past it)
            acc = min(self.accept(self.rounds, b), win[b])
            assert 1 <= acc <= K
            self.drafted += win[b] - 1
            self.accepted += acc - 1
            # the engine's on-device masking: emit through the first stop in
            # the accepted window, never past the budget
            stop_at = K
            for j in range(min(acc, len(window_toks))):
                if req.stop_token is not None and \
                        window_toks[j] == req.stop_token:
                    stop_at = j
                    break
            emit = min(acc, stop_at + 1, remaining)
            n_acc[b], n_emit[b] = acc, emit
            s["emitted"] = t + emit
            stopped = stop_at < emit
            if stopped or req.max_new_tokens - s["emitted"] <= 0:
                state[b] = None
            else:
                alive[b] = True
        self.rounds += 1
        return state, tokens, n_acc, n_emit, alive


def _make_workload(rng: random.Random, n_reqs: int):
    """Requests with unique scripted streams; some stop early, some have a
    zero budget (must complete without ever occupying a slot)."""
    reqs, want = [], []
    for rid in range(n_reqs):
        budget = rng.randint(0, 9) if rng.random() < 0.15 else rng.randint(1, 9)
        script = [rid * 1000 + t for t in range(max(budget, 1))]
        stop = None
        expected = script[:budget]
        if budget and rng.random() < 0.4:  # stop token somewhere mid-stream
            k = rng.randint(0, budget - 1)
            stop = script[k]
            expected = script[:k + 1]
        r = Request(prompt=[1], max_new_tokens=budget, stop_token=stop)
        r._script = script
        reqs.append(r)
        want.append(expected)
    return reqs, want


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(0, 14), st.integers(0, 10_000))
def test_scheduler_invariants(batch, n_reqs, seed):
    """No token loss or duplication, FIFO admission, every request completes,
    live slots are never clobbered (asserted inside FakeBackend)."""
    rng = random.Random(seed)
    backend = FakeBackend(batch)
    reqs, want = _make_workload(rng, n_reqs)
    streamed = {id(r): [] for r in reqs}
    sched = ContinuousScheduler(
        backend, on_token=lambda r, t: streamed[id(r)].append(t))
    for r in reqs:
        sched.submit(r)
    done = sched.run(max_steps=10_000)

    assert len(done) == len(reqs)
    for r, w in zip(reqs, want):
        assert r.done
        assert r.out == w, "token stream lost/duplicated/reordered"
        assert streamed[id(r)] == w  # streaming callback saw the same tokens
    # FIFO: admission order == submission order, minus zero-budget requests
    # (they complete immediately without taking a slot)
    assert backend.admitted == [r for r in reqs if r.max_new_tokens > 0]
    assert sched.stats.emitted_tokens == sum(len(w) for w in want)
    assert sched.stats.completed == len(reqs)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 10), st.integers(0, 10_000))
def test_scheduler_mid_run_submission(batch, n_extra, seed):
    """submit() between steps (random arrivals) preserves FIFO and loses
    nothing — the admission-queue half of continuous batching."""
    rng = random.Random(seed)
    backend = FakeBackend(batch)
    initial, want_i = _make_workload(rng, 3)
    extra, want_e = _make_workload(rng, n_extra)
    sched = ContinuousScheduler(backend)
    for r in initial:
        sched.submit(r)
    arrivals = list(extra)
    steps = 0
    while sched.pending or arrivals:
        if arrivals and rng.random() < 0.5:
            sched.submit(arrivals.pop(0))
        sched.step()
        steps += 1
        assert steps < 10_000
    for r, w in zip(initial + extra, want_i + want_e):
        assert r.done and r.out == w
    admitted_nonzero = [r for r in initial + extra if r.max_new_tokens > 0]
    # extras arrive one at a time in order, so FIFO still == submission order
    assert backend.admitted == admitted_nonzero


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(0, 12), st.integers(2, 4),
       st.integers(0, 10_000))
def test_spec_scheduler_invariants(batch, n_reqs, spec_k, seed):
    """The speculative protocol under the same invariants as the scalar
    one: ragged 1..spec_k windows reassemble into exactly the scripted
    streams (no loss, duplication, reordering, or poison past n_emit), FIFO
    admission holds, and the acceptance tallies match the backend's own
    ground truth."""
    rng = random.Random(seed)
    backend = FakeSpecBackend(batch, spec_k=spec_k)
    reqs, want = _make_workload(rng, n_reqs)
    streamed = {id(r): [] for r in reqs}
    sched = ContinuousScheduler(
        backend, on_token=lambda r, t: streamed[id(r)].append(t))
    for r in reqs:
        sched.submit(r)
    sched.run(max_steps=10_000)
    for r, w in zip(reqs, want):
        assert r.done
        assert r.out == w, "ragged emission lost/duplicated/reordered tokens"
        assert streamed[id(r)] == w
    assert backend.admitted == [r for r in reqs if r.max_new_tokens > 0]
    assert sched.stats.emitted_tokens == sum(len(w) for w in want)
    assert sched.stats.spec_rounds == sched.stats.decode_steps
    assert sched.stats.drafted_tokens == backend.drafted
    assert sched.stats.accepted_drafted_tokens == backend.accepted
    assert sum(sched.stats.accepted_by_rid.values()) == backend.accepted
    assert set(sched.stats.accepted_by_rid) <= {r.rid for r in reqs}


def test_spec_budget_exhausted_mid_window():
    """A round that accepts MORE tokens than the request's remaining budget
    must emit exactly the remainder, mark the request done, and free the
    slot for the next queued request — the clip happens at n_emit while
    n_acc (and the acceptance stats) keep the full accepted count."""
    backend = FakeSpecBackend(1, spec_k=4, accept=lambda rnd, b: 4)
    first = Request(prompt=[1], max_new_tokens=6)   # 6 = 4 + (2: mid-window)
    first._script = list(range(100, 110))
    second = Request(prompt=[1], max_new_tokens=3)
    second._script = list(range(200, 210))
    sched = ContinuousScheduler(backend)
    sched.submit(first)
    sched.submit(second)
    sched.run(max_steps=100)
    assert first.done and first.out == list(range(100, 106))
    assert second.done and second.out == list(range(200, 203))
    # round 2 accepted 4 but emitted 2 — stats keep the accepted count
    assert sched.stats.accepted_drafted_tokens == backend.accepted
    assert sched.stats.emitted_tokens == 9
    # budget clipping stranded slots mid-round, yet no round was wasted:
    # first took ceil rounds, second refilled the freed slot afterwards
    assert sched.stats.spec_rounds == 2 + 1


def test_spec_stop_token_mid_window():
    """A stop token in the middle of an accepted window: emit through the
    stop (inclusive), never past it, and free the slot that same round."""
    backend = FakeSpecBackend(1, spec_k=4, accept=lambda rnd, b: 4)
    req = Request(prompt=[1], max_new_tokens=8, stop_token=102)
    req._script = list(range(100, 110))  # stop sits at window position 2
    sched = ContinuousScheduler(backend)
    sched.submit(req)
    sched.run(max_steps=100)
    assert req.done
    assert req.out == [100, 101, 102], "must stop AT the stop token"
    assert sched.stats.spec_rounds == 1
    assert sched.stats.emitted_tokens == 3


def test_submit_completed_request_rejected():
    sched = ContinuousScheduler(FakeBackend(1))
    r = Request(prompt=[1], max_new_tokens=1)
    r.done = True
    with pytest.raises(ValueError):
        sched.submit(r)


def test_admission_only_steps_are_counted():
    """A step that only advances admission (no live slot yet) must still bump
    ``stats.steps`` — the regression was an early return that skipped the
    tally, so benchmark tok/step silently inflated.  It lands in
    ``admission_steps`` so ``decode_steps`` stays honest."""
    backend = FakeBackend(1)
    sched = ContinuousScheduler(backend)
    sched.step()  # nothing queued, nothing active: pure-admission step
    assert sched.stats.steps == 1
    assert sched.stats.admission_steps == 1
    assert sched.stats.decode_steps == 0


def test_queue_wait_recorded_per_rid_under_pure_fifo():
    """Every admitted request gets a queue-wait entry keyed on its rid,
    including under pure FIFO admission on an atomic backend (the per-tenant
    analysis joins on this map — a gap here silently reports zero waits).
    An injected virtual clock makes the waits exact."""
    now = [0.0]
    backend = FakeBackend(1)  # atomic admission, no prefix_match_len
    sched = ContinuousScheduler(backend, cache_affinity=False,
                                clock=lambda: now[0])
    reqs = []
    for i in range(3):
        r = Request(prompt=[1], max_new_tokens=2)
        r._script = [i * 10, i * 10 + 1]
        reqs.append(r)
        sched.submit(r)
    # B=1: request i waits while 0..i-1 run (2 steps each); tick the clock
    # one unit per scheduler step
    while sched.pending:
        sched.step()
        now[0] += 1.0
    waits = sched.stats.queue_wait_by_rid
    assert set(waits) == {r.rid for r in reqs}, "a FIFO admission went "\
        "unrecorded"
    assert len(sched.stats.queue_wait_s) == len(reqs)
    # admission happens at the START of the step that seats the request:
    # req0 at t=0, req1 after 2 decode steps (t=2), req2 at t=4
    assert [waits[r.rid] for r in reqs] == [0.0, 2.0, 4.0]


def test_dynamic_spec_k_shrinks_low_acceptance_window():
    """Dynamic spec_k (ROADMAP speculative follow-on (c)): a request whose
    drafts keep getting rejected must shrink its window to the floor (2)
    while a fully-accepted co-batched request keeps the full spec_k.  The
    accept function keys on slot: slot 0 always accepts only the free
    token, slot 1 accepts everything the window allows."""
    K = 5
    backend = FakeSpecBackend(2, spec_k=K,
                              accept=lambda rnd, b: 1 if b == 0 else K)
    low = Request(prompt=[1], max_new_tokens=12)
    low._script = list(range(100, 120))
    high = Request(prompt=[1], max_new_tokens=12)
    high._script = list(range(200, 220))
    sched = ContinuousScheduler(backend, dynamic_spec_k=True)
    sched.submit(low)
    sched.submit(high)
    sched.run(max_steps=100)
    assert low.out == list(range(100, 112))
    assert high.out == list(range(200, 212))
    lw, hw = backend.windows_seen[low.rid], backend.windows_seen[high.rid]
    # both start optimistic at the full window...
    assert lw[0] == K and hw[0] == K
    # ...the rejected request decays to the floor and stays there...
    assert lw[-1] == 2 and min(lw) == 2
    assert all(a >= b for a, b in zip(lw, lw[1:])), \
        f"low-acceptance window must shrink monotonically, got {lw}"
    # ...while the fully-accepted one never leaves the full window
    assert all(w == K for w in hw), hw
    # drafted-token accounting charges the shrunken window, not spec_k
    assert sched.stats.drafted_tokens == backend.drafted
    assert sched.stats.drafted_tokens < sched.stats.spec_rounds * 2 * (K - 1)
    assert sched.stats.spec_window_by_rid[low.rid] == 2
    assert sched.stats.spec_window_by_rid[high.rid] == K


def test_dynamic_spec_k_rejects_window_unaware_backend():
    """Enabling dynamic_spec_k on a speculative backend that cannot take
    per-slot windows must fail loudly at construction, not silently run
    fixed-K."""

    class NoWindow(FakeSpecBackend):
        spec_window_aware = False

    with pytest.raises(ValueError, match="spec_window_aware"):
        ContinuousScheduler(NoWindow(2, spec_k=3), dynamic_spec_k=True)
    # non-speculative backends ignore the flag entirely
    ContinuousScheduler(FakeBackend(2), dynamic_spec_k=True)


# ---------------------------------------------------------------------------
# real engine: overflow guard, queued serving, differential oracle
# ---------------------------------------------------------------------------


def _tiny_engine(key, B=2, max_len=48, window=0, prefill_chunk=32):
    cfg = get_smoke_config("bitnet-b1.58-2b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32, window=window)
    sp = quantize_for_serving(init_params(cfg, key), cfg)
    return DecodeEngine(sp, cfg, batch_size=B, max_len=max_len,
                        matmul_policy="fixed:ref",
                        prefill_chunk=prefill_chunk)


def test_run_overflow_raises_value_error(key):
    """run() must raise a real ValueError (not a bare assert, which vanishes
    under python -O) when handed more requests than slots."""
    eng = _tiny_engine(key, B=2)
    reqs = [Request(prompt=[3], max_new_tokens=1) for _ in range(3)]
    with pytest.raises(ValueError, match="batch_size"):
        eng.run(reqs)


def test_admit_rejects_overlong_request(key):
    eng = _tiny_engine(key, B=2, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.serve([Request(prompt=[3, 4, 5, 6], max_new_tokens=8)])
    # generational run() enforces the same bound (out-of-range positions
    # would silently scatter-drop their KV writes otherwise)
    with pytest.raises(ValueError, match="max_len"):
        eng.run([Request(prompt=[3] * 6, max_new_tokens=8)])


def _single_request_oracle(eng, prompt, max_new, stop=None,
                           return_logits=False):
    """The seed generational semantics: one request alone through prefill +
    scalar-index decode_step, greedy."""
    sp, cfg = eng.params, eng.cfg
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    cache, logits = prefill(sp, cfg, {"tokens": toks}, s_max=eng.max_len)
    out, logs, pos = [], [], len(prompt) - 1
    for _ in range(max_new):
        logs.append(np.asarray(logits[0], np.float32))
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
        if stop is not None and tok == stop:
            break
        pos += 1
        logits, cache = decode_step(sp, cfg, cache,
                                    jnp.asarray([tok], jnp.int32),
                                    jnp.asarray(pos, jnp.int32))
    return (out, logs) if return_logits else out


def _assert_matches_oracle_up_to_ties(eng, req):
    """Long-horizon check: the scheduler's stream must equal the
    single-request oracle, except that it may diverge where greedy argmax is
    numerically TIED (bf16 tiny-model logit collisions — batched vs B=1
    accumulation order then legitimately picks a different winner; any
    divergence with a real logit gap is a scheduler bug)."""
    out, logs = _single_request_oracle(eng, req.prompt, req.max_new_tokens,
                                       return_logits=True)
    assert len(out) == len(req.out)
    for j, (a, b) in enumerate(zip(out, req.out)):
        if a == b:
            continue
        lg = logs[j]
        assert abs(lg[a] - lg[b]) <= 1e-3, (
            f"token {j}: oracle {a} (logit {lg[a]}) vs scheduler {b} "
            f"(logit {lg[b]}) — divergence without an argmax tie")
        return  # tie hit: later tokens legitimately differ

def test_continuous_matches_single_request_oracle(key):
    """Differential oracle: greedy outputs from the continuous scheduler are
    IDENTICAL per request to running each request alone (mixed prompt
    lengths and budgets, more requests than slots → mid-flight refills)."""
    eng = _tiny_engine(key, B=2)
    specs = [([3, 4, 5], 6), ([7], 4), ([9, 2, 11, 4], 5), ([6, 6], 7),
             ([12, 13, 14], 3)]
    want = [_single_request_oracle(eng, p, n) for p, n in specs]
    reqs = [Request(prompt=p, max_new_tokens=n) for p, n in specs]
    eng.serve(reqs, max_steps=200)
    for r, w in zip(reqs, want):
        assert r.done and r.out == w, (r.out, w)


def test_continuous_stop_token_matches_oracle(key):
    """A stop token must free the slot at the same step the oracle stops."""
    eng = _tiny_engine(key, B=2)
    base = _single_request_oracle(eng, [3, 4, 5], 6)
    # the first greedy token after the first that has not appeared before —
    # learned, so the test is model-free (a repeat of an earlier token
    # would stop the stream there instead)
    j = next(i for i in range(1, len(base)) if base[i] not in base[:i])
    stop = base[j]
    want = _single_request_oracle(eng, [3, 4, 5], 6, stop=stop)
    assert want == base[:j + 1]
    r = Request(prompt=[3, 4, 5], max_new_tokens=6, stop_token=stop)
    other = Request(prompt=[7], max_new_tokens=4)
    eng.serve([r, other], max_steps=200)
    assert r.out == want
    assert other.out == _single_request_oracle(eng, [7], 4)


def test_continuous_matches_oracle_windowed(key):
    """Differential oracle, windowed config with prompts LONGER than the
    window — prefill wraps the ring, exactly where the slot-invariant bug
    hid: decode after a misaligned prefill silently dropped one attended
    in-window key per step.  Greedy streams must match the single-request
    oracle (up to bf16 argmax ties: multi-chunk admission merges attention
    chunks in a different order than whole-prompt prefill)."""
    eng = _tiny_engine(key, B=2, window=8, prefill_chunk=8)
    specs = [([3, 4, 5, 6, 7, 8, 9, 10, 11, 12], 6),  # S=10 >= CL=8: wraps
             ([7, 2], 4),
             ([9, 2, 11, 4, 13, 6, 15, 8, 17], 5),    # S=9: wraps mid-chunk
             ([6, 6, 6], 7)]
    reqs = [Request(prompt=p, max_new_tokens=n) for p, n in specs]
    eng.serve(reqs, max_steps=200)
    for r in reqs:
        assert r.done and len(r.out) == r.max_new_tokens
        _assert_matches_oracle_up_to_ties(eng, r)


def test_admission_compiles_one_trace_per_bucket(key):
    """Bucketed admission: a mixed-length request stream must compile the
    chunked prefill exactly once (one chunk shape = one bucket) and the
    commit exactly once — not one trace per prompt length, which is what the
    whole-prompt fallback path costs."""
    eng = _tiny_engine(key, B=2, prefill_chunk=4)
    reqs = [Request(prompt=[2 + j for j in range(1 + i)], max_new_tokens=2)
            for i in range(7)]  # prompt lengths 1..7: 1- and 2-chunk buckets
    eng.serve(reqs, max_steps=400)
    assert all(r.done and len(r.out) == 2 for r in reqs)
    assert eng.chunked_admission
    assert eng.trace_counts["prefill_chunk"] == 1, eng.trace_counts
    assert eng.trace_counts["admit_commit"] == 1, eng.trace_counts
    # the retracing whole-prompt fallback was never taken
    assert eng.trace_counts["prefill"] == 0, eng.trace_counts
    assert eng.trace_counts["sched_step"] == 1, eng.trace_counts


def test_admission_budget_interleaves_decode_with_long_prefill(key):
    """With an admission budget, a long arriving prompt is prefilled a chunk
    at a time while the co-batched live request keeps emitting tokens — its
    time-to-next-token stays bounded — and every stream still matches the
    single-request oracle."""
    eng = _tiny_engine(key, B=2, prefill_chunk=2, max_len=64)
    short = Request(prompt=[3], max_new_tokens=10)
    long = Request(prompt=[5 + i for i in range(12)], max_new_tokens=4)

    sched = ContinuousScheduler(eng, admission_budget=1)
    order = []
    short.on_token = lambda r, t: order.append("s")
    long.on_token = lambda r, t: order.append("l")
    sched.submit(short)
    sched.submit(long)
    steps = 0
    while sched.pending:
        sched.step()
        steps += 1
        assert steps < 200
    assert short.done and long.done
    # the long prompt needed 6 chunks at budget 1; the short request decoded
    # throughout, so its first several tokens precede long's first token
    first_l = order.index("l")
    assert first_l >= 5, order
    assert sched.stats.prefill_chunks >= 6 + 1  # long (6) + short (1)
    _assert_matches_oracle_up_to_ties(eng, short)
    _assert_matches_oracle_up_to_ties(eng, long)


def test_run_marks_budget_exhausted_requests_done(key):
    """run() regression: a request that spends its whole budget WITHOUT a
    stop-token hit must come back ``done`` — it used to stay not-done, so
    resubmitting it to a scheduler double-served it (duplicate tokens
    appended after the completed stream)."""
    eng = _tiny_engine(key, B=2)
    reqs = [Request(prompt=[3, 4], max_new_tokens=3),
            Request(prompt=[7], max_new_tokens=2)]
    eng.run(reqs)
    for r in reqs:
        assert len(r.out) == r.max_new_tokens
        assert r.done, "budget-exhausted request left not-done by run()"
    # ...which is exactly what the scheduler's resubmission guard keys on
    sched = ContinuousScheduler(eng)
    with pytest.raises(ValueError, match="completed"):
        sched.submit(reqs[0])


def test_chunked_admission_steps_counted_separately(key):
    """Steps that only advance a long prompt's prefill chunks (budget 1, no
    live slot) count in ``stats.steps`` AND ``stats.admission_steps``;
    ``decode_steps`` equals the steps that actually emitted tokens."""
    eng = _tiny_engine(key, B=1, prefill_chunk=2)
    long = Request(prompt=[5 + i for i in range(8)], max_new_tokens=4)
    sched = ContinuousScheduler(eng, admission_budget=1)
    sched.submit(long)
    sched.run(max_steps=100)
    assert long.done and len(long.out) == 4
    # 8-token prompt at chunk 2 / budget 1 → ≥ 3 steps with no decode yet
    assert sched.stats.admission_steps >= 3, sched.stats
    assert sched.stats.decode_steps == \
        sched.stats.steps - sched.stats.admission_steps
    # B=1, single request: every decode step emitted exactly one token
    assert sched.stats.decode_steps == sched.stats.emitted_tokens, sched.stats


def test_prefill_into_slot_splices_one_row(key):
    """The standalone atomic refill API: prefill one request and splice it
    into a single batch row — the other rows stay bit-identical and the
    spliced row equals a fresh single-request prefill."""
    from repro.models.decode import prefill, prefill_into_slot

    eng = _tiny_engine(key, B=2)
    sp, cfg = eng.params, eng.cfg
    toks = jnp.asarray([[3, 4, 5], [7, 8, 9]], jnp.int32)
    cache, _ = prefill(sp, cfg, {"tokens": toks}, s_max=eng.max_len)
    new_prompt = jnp.asarray([[11, 12]], jnp.int32)
    spliced, logits1 = prefill_into_slot(sp, cfg, cache,
                                         {"tokens": new_prompt},
                                         jnp.asarray(1, jnp.int32),
                                         s_max=eng.max_len)
    alone, logits_alone = prefill(sp, cfg, {"tokens": new_prompt},
                                  s_max=eng.max_len)
    for leaf in ("k", "v", "pos"):
        np.testing.assert_array_equal(  # untouched row is bit-identical
            np.asarray(spliced[leaf][:, 0], np.float32),
            np.asarray(cache[leaf][:, 0], np.float32))
        np.testing.assert_array_equal(  # spliced row == solo prefill row
            np.asarray(spliced[leaf][:, 1], np.float32),
            np.asarray(alone[leaf][:, 0], np.float32))
    np.testing.assert_array_equal(np.asarray(logits1),
                                  np.asarray(logits_alone[0]))


def test_fallback_arch_whole_prompt_admission(key):
    """Architectures without chunked-prefill support (recurrent state:
    zamba2) admit through the whole-prompt fallback — same commit splice,
    same oracle guarantees, `pending` returned as None from
    sched_admit_start."""
    cfg = get_smoke_config("zamba2-2.7b").with_(window=8, remat=False)
    sp = quantize_for_serving(init_params(cfg, key), cfg)
    eng = DecodeEngine(sp, cfg, batch_size=2, max_len=48,
                       matmul_policy="fixed:ref")
    assert not eng.chunked_admission
    # zamba2 prompts must be >= ssm_conv - 1 (conv state needs that many
    # tokens; a pre-existing prefill limitation, not an admission one)
    reqs = [Request(prompt=[3, 4, 5], max_new_tokens=3),
            Request(prompt=[7, 8, 9, 10], max_new_tokens=2),
            Request(prompt=[9, 2, 4, 6, 8], max_new_tokens=2)]
    eng.serve(reqs, max_steps=100)
    for r in reqs:
        assert r.done and len(r.out) == r.max_new_tokens
        _assert_matches_oracle_up_to_ties(eng, r)
    assert eng.trace_counts["prefill_chunk"] == 0
    # whole-prompt fallback retraces per distinct prompt length (3 here:
    # plens 3, 4, 5) — the cost the chunked path avoids
    assert eng.trace_counts["prefill"] == 3, eng.trace_counts


def test_scheduler_refills_freed_slots(key):
    """More requests than slots must still all complete, with admissions
    strictly FIFO and ≤ B slots ever active."""
    eng = _tiny_engine(key, B=2)
    reqs = [Request(prompt=[2 + i], max_new_tokens=2 + (i % 3))
            for i in range(5)]
    sched = ContinuousScheduler(eng)
    for r in reqs:
        sched.submit(r)
    max_active = 0
    steps = 0
    while sched.pending:
        sched.step()
        max_active = max(max_active, sched.num_active)
        steps += 1
        assert steps < 200
    assert all(r.done and len(r.out) == r.max_new_tokens for r in reqs)
    assert sched.admission_order == reqs  # FIFO
    assert max_active <= 2
    # continuous batching used fewer steps than summed sequential decode
    assert sched.stats.steps < sum(r.max_new_tokens for r in reqs)


# ---------------------------------------------------------------------------
# tier-2 soak (slow marker — scheduled CI job, excluded from tier-1)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_soak_skewed_lengths_randomized_arrivals(key):
    """Many short + few long requests with randomized mid-run arrivals; every
    request completes with exactly its budgeted tokens and matches the
    single-request oracle on a sampled subset."""
    eng = _tiny_engine(key, B=3, max_len=96)
    rng = random.Random(0)
    reqs = []
    for i in range(24):
        long = i % 8 == 7  # few long, many short
        prompt = [2 + (i % 19), 3 + (i % 11)][: 1 + i % 2]
        reqs.append(Request(prompt=prompt,
                            max_new_tokens=rng.randint(24, 32) if long
                            else rng.randint(1, 4)))
    sched = ContinuousScheduler(eng)
    pending = list(reqs)
    for _ in range(3):  # a few requests are present at t=0
        sched.submit(pending.pop(0))
    steps = 0
    while sched.pending or pending:
        if pending and rng.random() < 0.4:
            sched.submit(pending.pop(0))
        sched.step()
        steps += 1
        assert steps < 2000, "soak did not drain"
    assert all(r.done and len(r.out) == r.max_new_tokens for r in reqs)
    assert sched.stats.emitted_tokens == sum(r.max_new_tokens for r in reqs)
    assert sched.admission_order == reqs  # arrivals were in submission order
    for r in rng.sample(reqs, 4):  # spot-check decode correctness
        _assert_matches_oracle_up_to_ties(eng, r)
