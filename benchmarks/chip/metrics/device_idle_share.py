"""1 - the union of the intervals in which an operation ran on the device
over the traced window, %, mean over chips (trace)."""

from benchmarks.chip import reading


def read(ctx):
    busy = reading.per_chip(ctx, lambda d: d.busy)
    if busy is None or not ctx.trace_window_s:
        return None
    return 100.0 * (1.0 - busy / ctx.trace_window_s)
