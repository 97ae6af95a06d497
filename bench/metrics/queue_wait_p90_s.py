"""Scheduler: 90th percentile of the queue wait, due time to admission, of
the requests due in the window (``SchedulerStats.queue_wait_by_rid``); one
still queued at the close counts with its wait so far."""

from bench.stats import percentile


def read(ctx):
    waits = ctx.win.queue_waits()
    return percentile(waits, 90) if waits else None
