"""Device time of the prefill-chunk program per thousand real prompt tokens
it absorbed, ms/ktok (trace, with the harness's count of real tokens)."""

from benchmarks.chip import reading


def read(ctx):
    t = reading.program_time(ctx, reading.PREFILL)
    toks = sum(s.chunk_real for s in ctx.traced_steps())
    if t is None or not t[1] or not toks:
        return None
    return 1e6 * t[0] / toks
