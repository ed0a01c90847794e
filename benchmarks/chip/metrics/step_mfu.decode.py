"""Model FLOPs of the decoded tokens over the decode program's device time
times the chips' bf16 peak, % (trace)."""

from benchmarks.chip import reading, work


def read(ctx):
    fl = 0.0
    for s in ctx.traced_steps():
        if s.decode_active:
            mean_ctx = s.decode_valid / s.decode_active
            fl += s.decode_active * work.token_flops(ctx.cfg, mean_ctx)
    return reading.mfu(ctx, fl, reading.DECODE)
