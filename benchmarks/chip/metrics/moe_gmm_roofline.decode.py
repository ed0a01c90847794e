"""``moe_gmm`` in the decode program: least time for the routed rows and
the weights of the experts they hit, over the kernel's trace time, %."""

from benchmarks.chip import reading


def read(ctx):
    fl, by = reading.decode_work(ctx, lambda s: reading.moe_decode(ctx, s))
    return reading.roofline(ctx, fl, by, reading.kernel_time(
        ctx, "moe_gmm", reading.DECODE))
