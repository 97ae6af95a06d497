"""Engine: mean host span of ``sched_step`` in the window (it ends in the
host pull of the step's tokens)."""


def read(ctx):
    spans = ctx.win.spans_in_window("sched_step")
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)
