"""Model FLOPs of the real prompt tokens prefilled over the prefill-chunk
program's device time times the chips' bf16 peak, % (trace)."""

from benchmarks.chip import reading, work


def read(ctx):
    fl = sum(work.prefill_flops(ctx.cfg, s.chunk_start, s.chunk_real,
                                s.chunk_last)
             for s in ctx.traced_steps() if s.chunk_real)
    return reading.mfu(ctx, fl, reading.PREFILL)
