"""Device time per decode step of the collective operations (collective
permutes, all-to-alls and the other collective kinds) inside the decode
program, counted by op kind so it reads the same whatever implements the
exchange, ms (trace, mean over chips)."""

from benchmarks.chip import reading, xtrace


def read(ctx):
    t = reading.program_time(ctx, reading.DECODE)
    coll = reading.per_chip(ctx, lambda d: xtrace.op_seconds(
        d, xtrace.is_collective, reading.DECODE))
    if t is None or not t[1] or not coll:
        return None
    return 1000.0 * coll / t[1]
