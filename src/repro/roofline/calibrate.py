"""Scan-aware cost calibration for the dry-run roofline.

XLA's ``cost_analysis()`` counts a ``while``-loop (``lax.scan``) body ONCE,
regardless of trip count — verified empirically on this container (a scan of
8 matmuls reports the FLOPs of 1). Our stacks scan over layer blocks, so raw
dry-run numbers undercount by ~n_layers.

Fix: lower small **calibration variants** of each config — every segment at
count 1, then each segment bumped to count 2 — and solve

    cost(c_1 … c_k) = base + Σ_s c_s · block_s

exactly from the differences. Remainder segments (e.g. gemma3's trailing
``LL``) are approximated as ``len(kinds_rem)/len(kinds_full)`` of the
matching full block — ≤2 of 62 layers, noise-level. The same extrapolation
applies to FLOPs, HBM bytes, and HLO-parsed collective bytes (collectives
inside the scan body also appear once in the HLO text).

All lowerings keep the REAL input shape and mesh, so embedding/LM-head and
batch-dependent costs sit in the (exact) base term.
"""

from __future__ import annotations

import dataclasses

from .analysis import collective_bytes_from_hlo


def _counts_of(cfg) -> list:
    from repro.models.transformer import segments_of
    segs = list(segments_of(cfg))
    if cfg.is_encoder_decoder:
        from repro.models.transformer import Segment
        segs.append(Segment(("B",), cfg.n_encoder_layers))  # encoder stack
    return segs


def _variant(cfg, seg_counts: list[int]):
    """Rebuild a config whose segments have the given counts (no remainder
    segments). seg_counts aligns with the NON-remainder segments of cfg plus
    the encoder segment for enc-dec archs."""
    if cfg.is_encoder_decoder:
        dec, enc = seg_counts
        return dataclasses.replace(cfg, n_layers=dec, n_encoder_layers=enc)
    if cfg.family == "hybrid":
        (k,) = seg_counts
        return dataclasses.replace(cfg, n_layers=k * (cfg.hybrid_period + 1))
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        kd, ke = seg_counts
        return dataclasses.replace(
            cfg, n_layers=kd + ke,
            moe=dataclasses.replace(cfg.moe, first_dense_layers=kd))
    if cfg.layer_pattern:
        (k,) = seg_counts
        return dataclasses.replace(cfg,
                                   n_layers=k * len(cfg.layer_pattern))
    (k,) = seg_counts
    return dataclasses.replace(cfg, n_layers=k)


def _main_segments(cfg) -> tuple[list, list]:
    """(main segments with their true counts, remainder segments)."""
    segs = _counts_of(cfg)
    if cfg.is_encoder_decoder:
        return segs, []          # [decoder, encoder], both exact
    if cfg.family == "hybrid" or cfg.layer_pattern:
        main, rem = segs[:1], segs[1:]
        return main, rem
    return segs, []


def _measure(cfg, shape, mesh, moe_impl: str) -> dict:
    import jax
    from repro.launch import specs as S

    # UNROLLED lowering: a lax.scan body is cost-counted once regardless of
    # trip count, so calibration variants must not scan. Donation matches
    # the full-model lowering (dryrun.run_one).
    step_fn, args = S.lowering_args(cfg, shape, mesh, moe_impl=moe_impl,
                                    unroll=True)
    donate = (0, 1) if shape.kind == "train" else (2,)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step_fn, donate_argnums=donate).lower(*args) \
            .compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": float(coll["link_bytes"]),
            "collective_by_kind": coll["by_kind"]}


def calibrated_cost(cfg, shape, mesh, moe_impl: str = "ep") -> dict:
    """Scan-corrected per-device cost terms for the REAL config.

    Returns {"flops", "bytes", "collective_bytes", "detail"}.
    """
    main, rem = _main_segments(cfg)
    k = len(main)
    base_counts = [1] * k
    base = _measure(_variant(cfg, base_counts), shape, mesh, moe_impl)
    blocks = []
    for i in range(k):
        counts = list(base_counts)
        counts[i] = 2
        hi = _measure(_variant(cfg, counts), shape, mesh, moe_impl)
        blocks.append({key: hi[key] - base[key]
                       for key in ("flops", "bytes", "collective_bytes")})

    out = {}
    for key in ("flops", "bytes", "collective_bytes"):
        total = base[key]
        for i, seg in enumerate(main):
            total += (seg.count - 1) * blocks[i][key]
        # Remainder segments ≈ fraction of the matching main block.
        for seg in rem:
            frac = len(seg.kinds) / len(main[0].kinds)
            total += seg.count * frac * blocks[0][key]
        out[key] = max(total, 0.0)
    out["detail"] = {"base": base, "blocks": blocks,
                     "main_counts": [s.count for s in main],
                     "remainder": [(list(s.kinds), s.count) for s in rem]}
    return out
