"""Device time of the decode program per execution, ms (trace)."""

from benchmarks.chip import reading


def read(ctx):
    t = reading.program_time(ctx, reading.DECODE)
    if t is None or not t[1]:
        return None
    return 1000.0 * t[0] / t[1]
