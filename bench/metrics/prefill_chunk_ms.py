"""Engine: mean host span of ``sched_admit_step`` in the window, each
blocked on its result (one prompt chunk)."""


def read(ctx):
    spans = ctx.win.spans_in_window("sched_admit_step")
    if not spans:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(spans)
