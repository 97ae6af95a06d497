"""Drive a scheduler backend through warm-up and one measured window.

The program is used through ``ContinuousScheduler(backend, on_token=...,
clock=...)`` alone.  The backend is wrapped in :class:`TimedBackend`, which
records the benchmark's own host spans around ``sched_step`` (ends in the
host pull of tokens) and ``sched_admit_step`` (blocked on its result when
``block`` is set, in traced runs).

Times are seconds after the traffic starts.  A token becomes visible at the
end of the scheduler step that emitted it.  The window is
``[warmup_s, warmup_s + seconds)``:

- ``served_tok_s``: output tokens of the requests due in the window that
  became visible before its close, over ``seconds`` (tokens of requests due
  in warm-up do not count, nor does a token visible at or after the close;
  in a closed loop a request is due when it is submitted);
- TTFT of a request due in the window is timed from its due time; one with
  no token by the close counts with its wait so far;
- TPOT is ``(last - first) / (tokens - 1)`` of each request that completed
  inside the window with two tokens or more.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from repro.serving.engine import Request
from repro.serving.scheduler import ContinuousScheduler

from bench import traffic as tr

#: backend calls the benchmark times
TIMED = ("sched_step", "sched_admit_step", "sched_admit_start")


@dataclass
class Span:
    name: str
    t0: float
    t1: float


class TimedBackend:
    """A ``ScheduleBackend`` that forwards to ``inner`` and records spans."""

    def __init__(self, inner, clock: Callable[[], float], *,
                 block: bool = False, annotate: bool = False):
        self._inner = inner
        self._clock = clock
        self._block = block
        self._annotate = annotate
        self.spans: list[Span] = []
        self.t0 = 0.0

    def __getattr__(self, name: str):
        attr = getattr(self._inner, name)
        if name in TIMED:
            return functools.partial(self._timed, name, attr)
        return attr

    def _timed(self, name: str, fn, *args):
        if self._annotate:
            import jax

            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                return self._call(name, fn, args)
        return self._call(name, fn, args)

    def _call(self, name, fn, args):
        t0 = self._clock()
        out = fn(*args)
        if self._block and name == "sched_admit_step":
            import jax

            jax.block_until_ready(out)
        self.spans.append(Span(name, t0 - self.t0, self._clock() - self.t0))
        return out


@dataclass
class Record:
    rid: int
    prompt: list[int]
    budget: int
    due: float
    in_window: bool
    submit: float | None = None
    done: float | None = None
    n_out: int = 0
    request: Request | None = None
    #: when each token became visible
    times: list[float] = field(default_factory=list)

    @property
    def first(self) -> float | None:
        return self.times[0] if self.times else None

    @property
    def last(self) -> float | None:
        return self.times[-1] if self.times else None

    def ttft(self, close: float) -> float:
        """Due to first token; with none by ``close``, the wait so far."""
        end = self.first if self.first is not None and self.first < close \
            else close
        return end - self.due

    def tpot(self) -> float | None:
        if self.done is None or self.n_out < 2:
            return None
        return (self.last - self.first) / (self.n_out - 1)


@dataclass
class Window:
    """What a drive measured."""
    open: float
    close: float
    records: list[Record]
    spans: list[Span]
    #: tokens of any request that became visible inside the window
    tokens_in_window: int = 0
    decode_steps_in_window: int = 0
    compiles_in_window: int = 0
    #: (submit - due) of each request submitted inside the window
    lateness: list[float] = field(default_factory=list)
    queued_at_open: int = 0
    queued_at_close: int = 0
    slots: int = 0
    stats: Any = None
    #: the clock's reading when the traffic started
    t0: float = 0.0

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def due_in_window(self) -> list[Record]:
        return [r for r in self.records if r.in_window]

    def attempted(self) -> list[Record]:
        """Requests due in the window or served a token inside it."""
        return [r for r in self.records if r.in_window or any(
            self.open <= t < self.close for t in r.times)]

    def completed_in_window(self) -> list[Record]:
        return [r for r in self.records
                if r.done is not None and self.open <= r.done < self.close]

    def served_tokens(self) -> int:
        """Output tokens of the requests due in the window that became
        visible before its close."""
        return sum(1 for r in self.due_in_window() for t in r.times
                   if t < self.close)

    def served_tok_s(self) -> float:
        return self.served_tokens() / self.seconds

    def ttfts(self) -> list[float]:
        return [r.ttft(self.close) for r in self.due_in_window()]

    def tpots(self) -> list[float]:
        return [t for t in (r.tpot() for r in self.completed_in_window())
                if t is not None]

    def spans_in_window(self, name: str) -> list[Span]:
        return [s for s in self.spans
                if s.name == name and self.open <= s.t1 < self.close]

    def queue_waits(self) -> list[float]:
        """Due to admission of each request due in the window; one still
        queued at the close counts with its wait so far."""
        by_rid = self.stats.queue_wait_by_rid
        out = []
        for r in self.due_in_window():
            w = by_rid.get(r.request.rid) if r.request is not None else None
            if w is not None and r.submit + w < self.close:
                out.append(r.submit + w - r.due)
            else:
                out.append(self.close - r.due)
        return out


class CompileCounter:
    """Counts JAX backend compiles (persistent-cache reads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def drive(backend, mix: dict, seed: int, vocab: int, *, seconds: float,
          warmup_s: float | None = None,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          compiles: CompileCounter | None = None,
          at: list[tuple[float, Callable[[], None]]] | None = None,
          block: bool = False, annotate: bool = False) -> Window:
    """Run ``mix`` against ``backend`` for its warm-up and a window of
    ``seconds``; returns the window's records and counters.

    ``at`` holds ``(t, fn)`` pairs: ``fn`` runs between scheduler steps, at
    the first chance after ``t`` (the traced run starts its profiler so)."""
    warmup_s = mix["warmup_s"] if warmup_s is None else warmup_s
    timed = TimedBackend(backend, clock, block=block, annotate=annotate)
    buffer: list[tuple[Request, int]] = []
    sched = ContinuousScheduler(timed, on_token=lambda r, t: buffer.append(
        (r, t)), clock=clock)
    tokens = tr.Tokens(seed, vocab)
    t_open, t_close = float(warmup_s), float(warmup_s) + float(seconds)
    records: dict[int, Record] = {}
    win = Window(open=t_open, close=t_close, records=[], spans=timed.spans,
                 slots=backend.batch_size, stats=sched.stats)
    pending_hooks = sorted(at or [], key=lambda p: p[0])

    if mix["loop"] == "open":
        warm, inside = tr.open_schedule(mix, warmup_s, seconds)
        plan = warm + inside
        cycle, depth = None, 0
    else:
        plan, cycle, depth = [], tr.closed_cycle(mix, seed), mix["queue_depth"]
    cursor = 0

    def submit(plen: int, olen: int, due: float, now: float):
        req = Request(prompt=tokens.draw(plen), max_new_tokens=olen)
        rec = Record(rid=req.rid, prompt=req.prompt, budget=olen, due=due,
                     in_window=t_open <= due < t_close, submit=now,
                     request=req)
        records[req.rid] = rec
        if t_open <= now < t_close:
            win.lateness.append(now - due)
        sched.submit(req)

    t0 = clock()
    timed.t0 = win.t0 = t0
    opened = False
    c_open = 0
    while True:
        # a hair of slack so that a sleep that ends on a due time submits
        now = clock() - t0 + 1e-6
        while pending_hooks and pending_hooks[0][0] <= now:
            pending_hooks.pop(0)[1]()
        if not opened and now >= t_open:
            opened = True
            win.queued_at_open = sched.num_queued
            c_open = compiles.count if compiles is not None else 0
        if now >= t_close:
            break
        if cycle is None:
            while cursor < len(plan) and plan[cursor].t <= now:
                a = plan[cursor]
                submit(a.prompt_len, a.output_len, a.t, now)
                cursor += 1
        else:
            while sched.num_queued < depth:
                plen, olen = cycle[cursor % len(cycle)]
                submit(plen, olen, now, now)
                cursor += 1
        if not sched.pending:
            nxt = plan[cursor].t if cursor < len(plan) else t_close
            if pending_hooks:
                nxt = min(nxt, pending_hooks[0][0])
            sleep(max(0.0, min(nxt, t_close) - now) + 2e-6)
            continue
        decode_steps0 = sched.stats.decode_steps
        finished = sched.step()
        t = clock() - t0
        in_win = t_open <= t < t_close
        for req, _tok in buffer:
            rec = records[req.rid]
            rec.n_out += 1
            rec.times.append(t)
        if in_win:
            win.tokens_in_window += len(buffer)
            if sched.stats.decode_steps > decode_steps0:
                win.decode_steps_in_window += 1
        buffer.clear()
        for req in finished:
            records[req.rid].done = t
    win.queued_at_close = sched.num_queued
    if compiles is not None:
        win.compiles_in_window = compiles.count - c_open
    while pending_hooks:
        pending_hooks.pop(0)[1]()
    win.records = sorted(records.values(), key=lambda r: r.rid)
    return win
