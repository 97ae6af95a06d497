"""The traffic generator and the measured window, on a fake backend with a
virtual clock."""

import numpy as np
import pytest

from bench import traffic
from bench import window as win_mod

CHAT = {"loop": "open", "arrivals": {"kind": "poisson", "rate_per_s": 4.0},
        "prompt_tokens": {"kind": "lognormal", "median": 20, "sigma": 0.8,
                          "min": 2, "max": 80},
        "output_tokens": {"kind": "lognormal", "median": 6, "sigma": 0.6,
                          "min": 2, "max": 20},
        "slots": 4, "max_len": 128, "warmup_s": 2.0}
DOCS = {"loop": "closed", "queue_depth": 3, "cycle": 16,
        "prompt_tokens": {"kind": "uniform", "min": 30, "max": 60},
        "output_tokens": {"kind": "uniform", "min": 3, "max": 9},
        "slots": 2, "max_len": 128, "warmup_s": 2.0}


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt


class FakeBackend:
    """Atomic admission costs ``admit_s`` per prompt token, a decode step
    ``step_s``; each slot emits its budget of tokens (token = position)."""

    def __init__(self, clock, batch_size=4, step_s=0.05, admit_s=0.001,
                 on_step=None):
        self.clock = clock
        self.batch_size = batch_size
        self.step_s = step_s
        self.admit_s = admit_s
        self.on_step = on_step

    def sched_start(self):
        return {"left": [0] * self.batch_size, "n": [0] * self.batch_size}

    def sched_admit(self, state, slot, req):
        self.clock.now += self.admit_s * len(req.prompt)
        state["left"][slot] = req.max_new_tokens
        state["n"][slot] = 0
        return state

    def sched_step(self, state):
        self.clock.now += self.step_s
        if self.on_step is not None:
            self.on_step()
        toks, alive = [], []
        for b in range(self.batch_size):
            toks.append(state["n"][b])
            state["n"][b] += 1
            state["left"][b] -= 1
            alive.append(state["left"][b] > 0)
        return state, np.array(toks), np.array(alive)


def drive(mix, seed=7, seconds=5.0, **kw):
    clock = Clock()
    be = FakeBackend(clock, batch_size=mix["slots"], **kw)
    w = win_mod.drive(be, mix, seed, 1000, seconds=seconds, clock=clock,
                      sleep=clock.sleep)
    return w, clock


def test_schedule_is_identical_for_the_same_seed():
    a = traffic.open_schedule(CHAT, 2.0, 5.0)
    b = traffic.open_schedule(CHAT, 2.0, 5.0)
    assert a == b
    assert traffic.closed_cycle(DOCS, 5) == traffic.closed_cycle(DOCS, 5)
    t1, t2 = traffic.Tokens(9, 50), traffic.Tokens(9, 50)
    assert t1.draw(12) == t2.draw(12)
    assert traffic.Tokens(9, 50).draw(12) != traffic.Tokens(10, 50).draw(12)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 17, 3**40])
def test_every_seed_offers_the_same_sizes(seed):
    warm, win = traffic.open_schedule(CHAT, 2.0, 5.0)
    # the drive offers every seed one schedule: only the tokens differ
    w0, _ = drive(CHAT, seed=0)
    ws, _ = drive(CHAT, seed=seed)
    assert [(r.due, len(r.prompt), r.budget) for r in w0.records] == [
        (r.due, len(r.prompt), r.budget) for r in ws.records]
    assert len(win) == 20
    assert win[0].t == 2.0 and all(2.0 <= a.t < 7.0 for a in win)
    assert sorted(traffic.closed_cycle(DOCS, seed)) == sorted(
        traffic.closed_cycle(DOCS, 0))


def test_ttft_counts_from_the_due_time():
    w, _ = drive(CHAT, admit_s=0.004)
    due = w.due_in_window()
    assert due
    for r in due:
        assert r.submit >= r.due
        if r.first is not None and r.first < w.close:
            assert r.ttft(w.close) == pytest.approx(r.first - r.due)
    # a slow server makes requests wait past their due time
    assert max(r.submit - r.due for r in w.records) > 0
    assert max(w.ttfts()) > max(r.first - r.submit for r in due
                                if r.first is not None and r.first < w.close)


def test_request_in_flight_at_the_close_counts_its_wait_so_far():
    w, _ = drive(CHAT, step_s=0.3)
    late = [r for r in w.due_in_window()
            if r.first is None or r.first >= w.close]
    assert late, "the slow server leaves requests without a token"
    for r in late:
        assert r.ttft(w.close) == pytest.approx(w.close - r.due)
    assert len(w.ttfts()) == len(w.due_in_window())


def tokens_visible_in_window(w):
    """The count the benchmark used before ``served_tok_s``: every token
    visible inside the window, whenever its request was due."""
    return sum(1 for r in w.records for t in r.times if w.open <= t < w.close)


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["open", "closed"])
def test_served_tok_s_counts_only_tokens_of_requests_due_in_the_window(mix):
    w, _ = drive(mix)
    due = [r for r in w.records if w.open <= r.due < w.close]
    served = sum(1 for r in due for t in r.times if t < w.close)
    # both kinds of token the count leaves out occur in this drive: those of
    # requests due in warm-up that became visible inside the window, and
    # those of requests due in the window that became visible at its close
    # or after
    assert any(w.open <= t < w.close for r in w.records if r.due < w.open
               for t in r.times)
    assert any(t >= w.close for r in due for t in r.times)
    assert w.due_in_window() == due
    assert 0 < w.served_tokens() == served < sum(len(r.times) for r in due)
    assert w.served_tok_s() == pytest.approx(served / 5.0)
    assert w.tokens_in_window == tokens_visible_in_window(w)


#: open loop whose warm-up requests are still decoding when the window opens
#: on a backend of 0.05 s a step, which finishes the window's requests well
#: before the close (as the chat cell does on the chip)
EDGE = {"loop": "open", "arrivals": {"kind": "poisson", "rate_per_s": 0.5},
        "prompt_tokens": {"kind": "uniform", "min": 8, "max": 16},
        "output_tokens": {"kind": "uniform", "min": 60, "max": 120},
        "slots": 4, "max_len": 256, "warmup_s": 4.0}


def test_a_faster_backend_never_serves_fewer_tokens():
    base, _ = drive(EDGE, seconds=20.0, step_s=0.05)
    assert any(r.due < base.open <= r.last for r in base.records), \
        "a warm-up request decodes into the window"
    due = base.due_in_window()
    assert base.served_tokens() == sum(r.budget for r in due)

    fast, _ = drive(EDGE, seconds=20.0, step_s=0.025)
    # the old count read the faster backend as the slower one: it finished
    # more of the warm-up's tokens before the window opened
    assert tokens_visible_in_window(fast) < tokens_visible_in_window(base)
    assert fast.served_tok_s() >= base.served_tok_s()

    slow, _ = drive(EDGE, seconds=20.0, step_s=0.1)
    assert slow.served_tokens() < sum(r.budget for r in slow.due_in_window())
    assert slow.served_tok_s() < base.served_tok_s()


def test_tpot_is_of_requests_completed_inside_the_window():
    w, _ = drive(CHAT)
    for r in w.completed_in_window():
        assert r.n_out == r.budget
        if r.n_out > 1:
            assert r.tpot() == pytest.approx((r.last - r.first)
                                             / (r.n_out - 1))
    assert len(w.tpots()) == sum(1 for r in w.completed_in_window()
                                 if r.n_out > 1)


def test_closed_loop_keeps_the_queue_full():
    w, _ = drive(DOCS)
    # refilled before every step; the step that crossed a boundary may have
    # admitted into the slots it freed
    for q in (w.queued_at_open, w.queued_at_close):
        assert DOCS["queue_depth"] - DOCS["slots"] <= q <= DOCS["queue_depth"]
    assert all(r.due == r.submit for r in w.records)


def test_a_compile_inside_the_window_is_reported():
    import jax
    import jax.numpy as jnp

    counter = win_mod.CompileCounter()
    clock = Clock()
    shapes = iter(range(1, 10_000))

    def compile_after_open():
        if clock.now > CHAT["warmup_s"] + 1.0:
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(next(shapes) + 7919))

    be = FakeBackend(clock, batch_size=4, on_step=compile_after_open)
    w = win_mod.drive(be, CHAT, 1, 1000, seconds=2.0, clock=clock,
                      sleep=clock.sleep, compiles=counter)
    assert w.compiles_in_window > 0

    quiet = win_mod.drive(FakeBackend(clock, batch_size=4), CHAT, 1, 1000,
                          seconds=2.0, clock=clock, sleep=clock.sleep,
                          compiles=counter)
    assert quiet.compiles_in_window == 0
