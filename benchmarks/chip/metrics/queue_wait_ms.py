"""Median over the requests due in the window of the wait from when each
was due to when its first prefill chunk was dispatched (host clock), ms;
a request never started counts as waiting for ever."""

import math


def read(ctx):
    waits = sorted((t.first_chunk - t.due) if t.first_chunk is not None
                   else math.inf for t in ctx.tracks
                   if t.in_window and t.due < ctx.seconds)
    if not waits:
        return None
    w = waits[math.ceil(0.5 * len(waits)) - 1]
    return None if math.isinf(w) else 1000.0 * w
