"""Operations and bytes that a layer needs for the work it was given,
whatever implements it. Each architecture's module (``arch/``) computes
them from the configuration's published sizes and the counts the harness
recorded; these functions find that module by the configuration's
``arch``.

A count is the work the layer needs, not what the program happens to do:
the expert FFN needs the rows that were routed (not the padded capacity)
and the weights of the experts those rows went to; decode attention needs
the valid part of each active slot's cache. So a later change that removes
a copy or replaces a kernel does not make these counts stale.
"""

from __future__ import annotations

from benchmarks.chip import arch

BYTES = {"bfloat16": 2, "float32": 4}


def expected_distinct(pairs: float, n_experts: int) -> float:
    """Expected number of distinct experts hit by ``pairs`` routed pairs
    spread uniformly over ``n_experts`` (each must be read at least once)."""
    return n_experts * (1.0 - (1.0 - 1.0 / n_experts) ** pairs)


def moe_ffn(cfg: dict, pairs: float, distinct: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the expert FFN of ONE MoE layer over ``pairs``
    routed rows that hit ``distinct`` of the experts held here."""
    return arch.find(cfg).moe_ffn(cfg, pairs, distinct)


def decode_attn(cfg: dict, valid: float, slots: int) -> tuple[float, float]:
    """(FLOPs, bytes) of single-query attention of ONE attention layer
    over ``slots`` active slots whose valid cache lengths sum to
    ``valid``."""
    return arch.find(cfg).decode_attn(cfg, valid, slots)


def token_flops(cfg: dict, context: float, logits: bool = True) -> float:
    """Model FLOPs of one token through every layer, attending ``context``
    earlier positions (itself included), with the output head when
    ``logits``."""
    return arch.find(cfg).token_flops(cfg, context, logits)


def prefill_flops(cfg: dict, start: int, n: int, last: bool) -> float:
    """Model FLOPs of prompt positions ``start .. start + n - 1``, with
    the output head only for the prompt's last position (``last``)."""
    return arch.find(cfg).prefill_flops(cfg, start, n, last)
