"""Operations and bytes that a layer needs for the work it was given,
whatever implements it. Computed from the configuration's published sizes
(keys of ``configs/<config>.json``) and the counts the harness recorded.

A count is the work the layer needs, not what the program happens to do:
the expert FFN needs the rows that were routed (not the padded capacity)
and the weights of the experts those rows went to; decode attention needs
the valid part of each active slot's cache. So a later change that removes
a copy or replaces a kernel does not make these counts stale.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def expected_distinct(pairs: float, n_experts: int) -> float:
    """Expected number of distinct experts hit by ``pairs`` routed pairs
    spread uniformly over ``n_experts`` (each must be read at least once)."""
    return n_experts * (1.0 - (1.0 - 1.0 / n_experts) ** pairs)


def moe_ffn(cfg: dict, pairs: float, distinct: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the grouped expert FFN of ONE layer: gate, up and
    down matmuls over ``pairs`` routed rows; weights of ``distinct``
    experts read once, each routed row read in and written out once."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    b = BYTES[cfg["torch_dtype"]]
    flops = pairs * 3 * 2 * d * f
    nbytes = distinct * 3 * d * f * b + pairs * 2 * d * b
    return flops, nbytes


def decode_attn(cfg: dict, valid: float, slots: int) -> tuple[float, float]:
    """(FLOPs, bytes) of single-query attention of ONE layer over
    ``slots`` active slots whose valid cache lengths sum to ``valid``:
    scores and the weighted sum over every valid key; each valid key and
    value read once, each query read and output written once."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    b = BYTES[cfg["torch_dtype"]]
    flops = 4 * valid * h * hd
    nbytes = 2 * valid * hkv * hd * b + 2 * slots * h * hd * b
    return flops, nbytes


def token_flops(cfg: dict, context: float, logits: bool = True) -> float:
    """Model FLOPs of one token through every layer, attending ``context``
    earlier positions (itself included): projections, attention scores and
    sum, router, the top-k experts, and the output head when ``logits``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = d // h
    f, e, k = (cfg["intermediate_size"], cfg["num_local_experts"],
               cfg["num_experts_per_tok"])
    per_layer = (2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d
                 + 4 * context * h * hd + 2 * d * e + k * 3 * 2 * d * f)
    head = 2 * d * cfg["vocab_size"] if logits else 0
    return cfg["num_hidden_layers"] * per_layer + head


def prefill_flops(cfg: dict, start: int, n: int, last: bool) -> float:
    """Model FLOPs of prompt positions ``start .. start + n - 1`` (counted
    in real prompt tokens, causal context), with the output head only for
    the prompt's last position (``last``): that is all prefill needs."""
    if n <= 0:
        return 0.0
    mean_ctx = start + (n + 1) / 2
    out = n * token_flops(cfg, mean_ctx, logits=False)
    if last:
        out += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return out
