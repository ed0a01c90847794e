"""Helpers shared by the per-layer metric readers (``metrics/*.py``).

Device numbers are means over the chips the cell uses, read from the
traced part of the window; host numbers come from the harness's records
of every step and request (``run.Context``).
"""

from __future__ import annotations

from benchmarks.chip import work, xtrace

DECODE = ("decode_step",)
# The engine jits the chunk program from a ``functools.partial``, which
# JAX names ``jit__unknown``; "prefill" keeps matching once it is named.
PREFILL = ("prefill", "_unknown")


def per_chip(ctx, fn) -> float | None:
    """Mean over the traced chips of ``fn(device)``; None without a trace."""
    if not ctx.trace:
        return None
    devs = list(ctx.trace.values())
    return sum(fn(d) for d in devs) / len(devs)


def program_time(ctx, programs) -> tuple[float, int] | None:
    """Mean device time of the named programs per chip, and their count."""
    if not ctx.trace:
        return None
    devs = list(ctx.trace.values())
    vals = [xtrace.module_seconds(d, programs) for d in devs]
    return sum(v[0] for v in vals) / len(devs), vals[0][1]


def kernel_time(ctx, kernel: str, programs) -> float | None:
    return per_chip(ctx, lambda d: xtrace.op_seconds(
        d, lambda n: kernel in n, programs))


def roofline(ctx, flops: float, nbytes: float, seconds: float | None):
    """Least time the chips need for the work over the time taken, in %;
    None where the work or the kernel is absent (never 0)."""
    if not seconds or not ctx.peaks or flops <= 0:
        return None
    t_min = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * t_min / seconds


def decode_work(ctx, layer_fn):
    """Sum over traced decode steps and layers of ``layer_fn(step)``'s
    (FLOPs, bytes)."""
    fl = by = 0.0
    n_layers = ctx.cfg["num_hidden_layers"]
    for s in ctx.traced_steps():
        if s.decode_active:
            f, b = layer_fn(s)
            fl += n_layers * f
            by += n_layers * b
    return fl, by


def moe_decode(ctx, s):
    k, e = ctx.cfg["num_experts_per_tok"], ctx.cfg["num_local_experts"]
    pairs = s.decode_active * k
    return work.moe_ffn(ctx.cfg, pairs, work.expected_distinct(pairs, e))


def moe_prefill(ctx, s):
    k, e = ctx.cfg["num_experts_per_tok"], ctx.cfg["num_local_experts"]
    pairs = s.chunk_real * k
    return work.moe_ffn(ctx.cfg, pairs, work.expected_distinct(pairs, e))


def mfu(ctx, flops: float, programs) -> float | None:
    t = program_time(ctx, programs)
    if t is None or not t[0] or not ctx.peaks or flops <= 0:
        return None
    return 100.0 * flops / (t[0] * ctx.peaks["bf16_flops_per_s"] * ctx.chips)
