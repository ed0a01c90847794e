"""Attention blocks: GQA (RoPE / M-RoPE / qk-norm / sliding window), MLA,
and encoder-decoder cross-attention — with train / prefill / decode modes.

Caches are fixed-capacity (batched serving): global layers allocate
``cap = seq_len`` slots, sliding-window layers a ``min(cap, window)`` ring
buffer (RoPE is applied at write time with absolute positions, so ring slots
need no re-rotation). MLA caches the **compressed latent** (kv_lora + rope
key) and decodes with the absorbed-matrix form — the memory win that makes
DeepSeek-V3 decode feasible.

Every cache write runs under ``jax.named_scope("cache_write")``; the
transformer stack calls these blocks under ``attn``, so a device trace
names the writes ``attn/cache_write``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (apply_mrope, apply_rope, attention_core, rmsnorm)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_attn(key, cfg, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h, hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hkv, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hkv, hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (h, hd, d), dtype) * (h * hd) ** -0.5,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_attn_cache(cfg, batch: int, cap: int, dtype) -> dict:
    return {
        "k": jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


def _cache_write(cache_arr, new, slot, pc):
    """Write one token into the cache at (traced) sequence index ``slot``.

    ``slot`` may be a scalar (whole batch at one position) or a (B,) vector
    (per-slot positions — continuous batching), in which case each batch row
    writes at its own index via a one-hot masked update.

    On a mesh, a dynamic_update_slice at a traced index into the
    seq-SHARDED cache dim triggers GSPMD "involuntary full
    rematerialization" — the whole cache is all-gathered and re-sharded
    every layer every step (~tens of GB/step). A one-hot masked update is
    elementwise, stays local to each shard, and decode streams the full
    cache for attention anyway (§Perf iteration 5).
    """
    with jax.named_scope("cache_write"):
        cap = cache_arr.shape[1]
        slot = jnp.asarray(slot)
        if slot.ndim == 1:
            mask = (jnp.arange(cap)[None, :] == slot[:, None]).reshape(
                (slot.shape[0], cap) + (1,) * (cache_arr.ndim - 2))
            return jnp.where(mask, new.astype(cache_arr.dtype), cache_arr)
        if pc is None or pc.mesh is None:
            idx = (0, slot) + (0,) * (cache_arr.ndim - 2)
            return jax.lax.dynamic_update_slice(cache_arr, new, idx)
        mask = (jnp.arange(cap) == slot).reshape(
            (1, cap) + (1,) * (cache_arr.ndim - 2))
        return jnp.where(mask, new.astype(cache_arr.dtype), cache_arr)


def _decode_attn_kernel(q, k, v, valid, pc):
    """Kernelized decode attention: stream the per-slot cache past the
    single query through ``kernels.ops.decode_attn_auto`` (Pallas
    flash-decode on TPU / interpret; jnp oracle on CPU — same masking math).

    A Pallas kernel is opaque to the partitioner, so under a mesh it runs
    per shard inside ``shard_map``: batch over the data axes and kv heads
    (with their query groups) over the model axis, each where it divides.
    """
    from repro.kernels.ops import decode_attn_auto

    kc = pc.kernels
    b = q.shape[0]
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32).reshape(-1), (b,))

    def attn(q, k, v, valid):
        return decode_attn_auto(q, k, v, valid, block_s=kc.block_s,
                                interpret=kc.interpret)

    if pc.mesh is None:
        return attn(q, k, v, valid)
    nb = 1
    for a in pc.data_axes:
        nb *= pc.mesh.shape[a]
    b_ax = pc.data_axes if pc.data_axes and b % nb == 0 else None
    h_ax = (pc.model_axis if pc.model_axis is not None
            and k.shape[2] % pc.mesh.shape[pc.model_axis] == 0 else None)
    q_spec, kv_spec = P(b_ax, h_ax, None), P(b_ax, None, h_ax, None)
    return jax.shard_map(attn, mesh=pc.mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(b_ax)),
                         out_specs=q_spec, check_vma=False)(q, k, v, valid)


def _rope_qk(cfg, q, k, pos, pos3):
    if cfg.mrope_sections is not None:
        if pos3 is None:  # pure text: all three position streams equal
            pos3 = jnp.broadcast_to(pos[None], (3,) + pos.shape)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k


def _head_constraint(t, pc):
    """Pin (B, S, H, hd) activations to batch×head sharding when divisible.

    with_sharding_constraint transposes to the SAME constraint on the
    cotangent, so this also pins the backward dq/dk/dv — without it GSPMD
    resolves the dW einsum by all-gathering full-batch activations in f32
    over the data axis (§Perf iteration 3).

    DENSE archs only: MoE stacks keep activations in the EP (data, model)
    token layout between layers, and pinning q/k/v to batch-over-data
    forces a per-layer reshard (probe: 5.1 → 38.2 GiB/layer on phi3.5
    train — §Perf it-7)."""
    if pc is None or pc.mesh is None or pc.model_axis is None \
            or pc.ep_axes:
        return t
    nb = 1
    for a in pc.data_axes:
        nb *= pc.mesh.shape[a]
    if nb == 0 or t.shape[0] % max(nb, 1):
        return t
    if t.shape[2] % pc.mesh.shape[pc.model_axis]:
        return pc.shard(t, pc.data_axes, None, None, None)
    return pc.shard(t, pc.data_axes, None, pc.model_axis, None)


def attn_block(p, x, *, cfg, pos, window=None, cache=None, length=None,
               mode="train", pos3=None, flash_block=1024, causal=True,
               pc=None):
    """GQA attention. x: (B, S, d); pos: (B, S) absolute positions.

    mode: "train" (no cache) | "prefill" (build cache) | "decode" (S == 1,
    read + update cache at ``length``). Returns (y, new_cache | None).
    ``causal=False`` → bidirectional (encoder layers).
    """
    b, s, _ = x.shape
    offset = 0 if causal else None
    q = _head_constraint(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), pc)
    k = _head_constraint(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), pc)
    v = _head_constraint(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), pc)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q, k = _rope_qk(cfg, q, k, pos, pos3)

    new_cache = None
    if mode == "train":
        out = attention_core(q, k, v, causal_offset=offset, window=window,
                             valid_len=None, flash_block=flash_block)
    elif mode == "prefill" and length is None:
        # Fresh one-shot prefill: attend the s chunk keys only (O(s^2), not
        # O(s*cap)) and write from offset 0 — the pre-chunking fast path.
        cap = cache["k"].shape[1]
        out = attention_core(q, k, v, causal_offset=offset, window=window,
                             valid_len=None, flash_block=flash_block)
        with jax.named_scope("cache_write"):
            if cap < s:
                # Ring buffer smaller than the prefill: keep the last cap
                # tokens (their slot indices are consecutive mod cap →
                # unique writes).
                kk, vv = k[:, s - cap:], v[:, s - cap:]
                slots = pos[0, s - cap:] % cap
                new_cache = {"k": cache["k"].at[:, slots].set(kk),
                             "v": cache["v"].at[:, slots].set(vv)}
            else:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k, (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v, (0, 0, 0, 0))}
    elif mode == "prefill":
        # Chunked CONTINUATION: the chunk's keys land at the current fill
        # level ``length`` and queries attend the cached prefix plus the
        # causal part of the chunk. causal_offset = start makes query i see
        # key j iff j <= start + i; valid_len covers the Sq == 1 single-
        # token-chunk case, where attention_core ignores causal_offset.
        # ``length`` may be a (B,) vector — per-slot offsets, each batch row
        # resuming its own chunked prefill. Wrapped rings can't continue
        # (slot positions become ambiguous);
        # Model.supports_chunked_prefill gates those shapes out upstream.
        cap = cache["k"].shape[1]
        if cap < s:
            raise ValueError("chunked prefill continuation into a cache "
                             f"smaller than the chunk ({cap} < {s})")
        start = length.astype(jnp.int32)
        with jax.named_scope("cache_write"):
            if start.ndim == 1:
                rows = jnp.arange(b)[:, None]
                idx = start[:, None] + jnp.arange(s)[None]   # (B, s)
                ck = cache["k"].at[rows, idx].set(
                    k.astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[rows, idx].set(
                    v.astype(cache["v"].dtype), mode="drop")
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
        out = attention_core(q, ck, cv, causal_offset=start,
                             window=window, valid_len=start + s,
                             flash_block=flash_block)
        new_cache = {"k": ck, "v": cv}
    else:  # decode: s == 1, absolute position == length
        cap = cache["k"].shape[1]
        if window is not None and cap <= window:
            slot = length % cap
        else:
            slot = jnp.minimum(length, cap - 1)
        ck = _cache_write(cache["k"], k, slot, pc)
        cv = _cache_write(cache["v"], v, slot, pc)
        new_cache = {"k": ck, "v": cv}
        valid = jnp.minimum(length + 1, cap)
        if pc is not None and pc.kernels is not None:
            out = _decode_attn_kernel(q[:, 0], ck, cv, valid, pc)[:, None]
        else:
            out = attention_core(q, ck, cv, causal_offset=None, window=None,
                                 valid_len=valid, flash_block=flash_block)
    out = _head_constraint(out, pc)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg, dtype) -> dict:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * d ** -0.5,
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": jax.random.normal(
            ks[1], (m.q_lora_rank, h, qk_head), dtype) * m.q_lora_rank ** -0.5,
        "wkv_a": jax.random.normal(
            ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype) * d ** -0.5,
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wk_b": jax.random.normal(
            ks[3], (m.kv_lora_rank, h, m.qk_nope_head_dim),
            dtype) * m.kv_lora_rank ** -0.5,
        "wv_b": jax.random.normal(
            ks[4], (m.kv_lora_rank, h, m.v_head_dim),
            dtype) * m.kv_lora_rank ** -0.5,
        "wo": jax.random.normal(
            ks[5], (h, m.v_head_dim, d), dtype) * (h * m.v_head_dim) ** -0.5,
    }


def init_mla_cache(cfg, batch: int, cap: int, dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, cap, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cap, m.qk_rope_head_dim), dtype),
    }


def _mla_qkv(p, x, cfg, pos):
    m = cfg.mla
    cq = rmsnorm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    ckv_full = x @ p["wkv_a"]
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_block(p, x, *, cfg, pos, cache=None, length=None, mode="train",
              flash_block=1024, pc=None, **_):
    """MLA attention. Direct form for train/prefill; absorbed for decode."""
    m = cfg.mla
    b, s, _ = x.shape
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, x, cfg, pos)

    new_cache = None
    if mode in ("train", "prefill"):
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
        h = k_nope.shape[2]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                      (b, s, h, m.qk_rope_head_dim))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        # attention_core assumes equal k/v head dims; pad v with zeros up to
        # the qk head size and slice the output back (exact, no bias).
        qk_dim = q.shape[-1]
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                            (0, qk_dim - m.v_head_dim)))
        out = attention_core(q, k, v_pad, causal_offset=0, window=None,
                             valid_len=None, flash_block=flash_block)
        out = out[..., :m.v_head_dim]
        if mode == "prefill":
            with jax.named_scope("cache_write"):
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice(
                        cache["ckv"], ckv, (0, 0, 0)),
                    "k_rope": jax.lax.dynamic_update_slice(
                        cache["k_rope"], k_rope, (0, 0, 0))}
    else:  # decode — absorbed-matrix form over the latent cache
        cap = cache["ckv"].shape[1]
        slot = jnp.minimum(length, cap - 1)
        cckv = _cache_write(cache["ckv"], ckv, slot, pc)
        ckr = _cache_write(cache["k_rope"], k_rope, slot, pc)
        new_cache = {"ckv": cckv, "k_rope": ckr}
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])   # absorb W^UK
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, cckv)
                  + jnp.einsum("bshk,btk->bhst", q_rope, ckr)) * scale
        vl = jnp.minimum(length + 1, cap)
        if jnp.ndim(vl) == 1:   # per-slot fill levels (continuous batching)
            valid = jnp.arange(cap)[None, :] < vl[:, None]       # (B, cap)
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        else:
            valid = jnp.arange(cap) < vl
            scores = jnp.where(valid[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
        ctx_lat = jnp.einsum("bhst,btr->bshr", probs.astype(cckv.dtype), cckv)
        out = jnp.einsum("bshr,rhk->bshk", ctx_lat, p["wv_b"])    # absorb W^UV

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------

def cross_attn_block(p, x, enc_kv, *, cfg, flash_block=1024):
    """Decoder cross-attention over precomputed encoder K/V (no mask)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = attention_core(q, enc_kv["k"], enc_kv["v"], causal_offset=None,
                         window=None, valid_len=None,
                         flash_block=flash_block)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_cross_kv(p, enc_out):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    return {"k": jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"]),
            "v": jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])}
