"""``decode_attn``: least time to read the valid cache of the active slots
and compute over it, over the kernel's trace time, %."""

from benchmarks.chip import reading, work


def read(ctx):
    fl, by = reading.decode_work(ctx, lambda s: work.decode_attn(
        ctx.cfg, s.decode_valid, s.decode_active))
    return reading.roofline(ctx, fl, by, reading.kernel_time(
        ctx, "decode_attn", reading.DECODE))
