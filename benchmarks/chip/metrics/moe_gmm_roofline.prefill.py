"""``moe_gmm`` in the prefill-chunk program: least time for the routed
real prompt rows and the experts they hit, over the kernel's trace
time, %."""

from benchmarks.chip import arch, reading


def read(ctx):
    fl = by = 0.0
    n = arch.find(ctx.cfg).moe_layers(ctx.cfg)
    for s in ctx.traced_steps():
        if s.chunk_real:
            f, b = reading.moe_prefill(ctx, s)
            fl, by = fl + n * f, by + n * b
    return reading.roofline(ctx, fl, by, reading.kernel_time(
        ctx, "moe_gmm", reading.PREFILL))
