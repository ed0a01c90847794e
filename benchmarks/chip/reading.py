"""Helpers shared by the per-layer metric readers (``metrics/*.py``).

Device numbers are means over the chips the cell uses, read from the
traced part of the window; host numbers come from the harness's records
of every step and request (``run.Context``).
"""

from __future__ import annotations

from benchmarks.chip import arch, work, xtrace

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


def decode_work(ctx, layer_fn, layers: int):
    """Sum over traced decode steps of ``layers`` times ``layer_fn(step)``'s
    (FLOPs, bytes): the work of one layer of a kind, over the ``layers``
    of that kind (``attn_layers`` or ``moe_layers`` of the
    architecture's module)."""
    fl = by = 0.0
    for s in ctx.traced_steps():
        if s.decode_active:
            f, b = layer_fn(s)
            fl += layers * f
            by += layers * b
    return fl, by


def _moe_rows(cfg, tokens: int):
    """The expert FFN's work for ``tokens`` tokens in ONE MoE layer: their
    routed pairs that land on the experts held here (each token takes k of
    the experts routed over, spread evenly) and the distinct held experts
    those pairs hit."""
    held, routed, k = arch.find(cfg).experts(cfg)
    pairs = tokens * k * held / routed
    return work.moe_ffn(cfg, pairs, work.expected_distinct(pairs, held))


def moe_decode(ctx, s):
    return _moe_rows(ctx.cfg, s.decode_active)


def moe_prefill(ctx, s):
    return _moe_rows(ctx.cfg, s.chunk_real)


def mfu(ctx, flops: float, programs) -> float | None:
    t = program_time(ctx, programs)
    if t is None or not t[0] or not ctx.peaks or flops <= 0:
        return None
    return 100.0 * flops / (t[0] * ctx.peaks["bf16_flops_per_s"] * ctx.chips)
