"""Architecture-generic stack: decoder / encoder-decoder / hybrid.

Layers are grouped into **segments** — the smallest repeating block of layer
kinds (e.g. gemma3's ``LLLLLG``; zamba2's ``MMMMMMA``; deepseek's 3 dense +
58 MoE). Parameters and caches are stacked per segment and the stack scans
over blocks with ``lax.scan``, keeping HLO size O(segment), not O(n_layers)
— essential for lowering 61–81-layer production configs.

Layer kinds:
  G global attention + FFN     L sliding-window attention + FFN
  D attention + dense FFN (MoE arch's leading dense layers)
  E attention + MoE FFN        M Mamba2 (SSD)
  A zamba2 shared attention block (parameters shared across occurrences)
  C decoder layer with cross-attention (encoder-decoder)
  B bidirectional encoder layer

Device work is named by ``jax.named_scope`` so a profiler trace can group
it: ``layer_cache`` (a serving scan's own work: each block's cache sliced
from the stacked cache and its new cache stacked back), ``layer_weights``
(a serving block's weights sliced from the stack; the MoE expert stacks
only for the dense and expert-parallel paths, since the kernel path reads
its layer's experts from the stack), ``attn`` with
``attn/cache_write`` inside it, the MoE layer's ``moe/*`` (``models.moe``)
and ``lm_head``. A trace names an op by the innermost of these.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import ssm as ssm_mod
from .layers import NO_PARALLEL, ParallelContext, ffn_apply, init_ffn, rmsnorm
from .moe import _is_experts_leaf, init_moe, moe_apply


# ---------------------------------------------------------------------------
# Segment structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    kinds: tuple[str, ...]
    count: int                       # number of scanned blocks


def segments_of(cfg) -> list[Segment]:
    """Decoder-side segment decomposition of the layer stack."""
    n = cfg.n_layers
    if cfg.is_encoder_decoder:
        return [Segment(("C",), n)]
    if cfg.family == "ssm":
        return [Segment(("M",), n)]
    if cfg.family == "hybrid":
        q = cfg.hybrid_period + 1
        segs = [Segment(("M",) * cfg.hybrid_period + ("A",), n // q)]
        if n % q:
            segs.append(Segment(("M",) * (n % q), 1))
        return segs
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        k = cfg.moe.first_dense_layers
        return [Segment(("D",), k), Segment(("E",), n - k)]
    if cfg.moe is not None:
        return [Segment(("E",), n)]
    if cfg.layer_pattern:
        p = len(cfg.layer_pattern)
        segs = [Segment(tuple(cfg.layer_pattern), n // p)]
        if n % p:
            segs.append(Segment(tuple(cfg.layer_pattern[: n % p]), 1))
        return segs
    return [Segment(("G",), n)]


def padded_vocab(cfg) -> int:
    """Vocab rounded up so the tensor axis and the MXU lane width divide it."""
    return -(-cfg.vocab // 256) * 256


def moe_layer_count(cfg) -> int:
    """Number of MoE layers, in the canonical stats order (segment-major,
    kind-major, block-major — the order ``forward(collect_moe_stats=True)``
    stacks per-layer routing counts in)."""
    return sum(seg.count * seg.kinds.count("E") for seg in segments_of(cfg))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_layer(key, kind: str, cfg, dtype) -> dict:
    from .layers import init_rmsnorm
    d = cfg.d_model
    if kind == "M":
        k1, = jax.random.split(key, 1)
        return {"ln": init_rmsnorm(d, dtype)["scale"],
                "mamba": ssm_mod.init_mamba(k1, cfg, dtype)}
    if kind == "A":
        return {}                                   # shared params used
    ks = jax.random.split(key, 4)
    init_a = attn_mod.init_mla if cfg.mla is not None else attn_mod.init_attn
    p = {"ln1": jnp.zeros((d,), dtype), "attn": init_a(ks[0], cfg, dtype),
         "ln2": jnp.zeros((d,), dtype)}
    if kind == "E":
        p["moe"] = init_moe(ks[1], d, cfg.moe, dtype)
    elif kind == "D":
        p["ffn"] = init_ffn(ks[1], d, cfg.moe.dense_d_ff, dtype)
    else:
        p["ffn"] = init_ffn(ks[1], d, cfg.d_ff, dtype)
    if kind == "C":
        p["ln_x"] = jnp.zeros((d,), dtype)
        p["xattn"] = attn_mod.init_attn(ks[2], cfg, dtype)
    return p


def _init_segment(key, seg: Segment, cfg, dtype):
    """Per-position stacked params: tuple of dicts, leaves (count, ...)."""
    out = []
    for i, kind in enumerate(seg.kinds):
        ks = jax.random.split(jax.random.fold_in(key, i), seg.count)
        out.append(jax.vmap(lambda k: _init_layer(k, kind, cfg, dtype))(ks))
    return tuple(out)


def init_params(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    vp = padded_vocab(cfg)
    keys = jax.random.split(key, 8)
    p = {
        "embed": jax.random.normal(keys[0], (vp, cfg.d_model), dtype) * 0.02,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "segments": tuple(
            _init_segment(jax.random.fold_in(keys[1], si), seg, cfg, dtype)
            for si, seg in enumerate(segments_of(cfg))
        ),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            keys[2], (cfg.d_model, vp), dtype) * cfg.d_model ** -0.5
    if cfg.family == "hybrid":                      # zamba2 shared block
        p["shared"] = _init_layer(keys[3], "G", cfg, dtype)
    if cfg.is_encoder_decoder:
        enc_seg = Segment(("B",), cfg.n_encoder_layers)
        p["encoder"] = {
            "segments": (_init_segment(keys[4], enc_seg, cfg, dtype),),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
    if cfg.input_mode != "text":
        p["frontend_proj"] = jax.random.normal(
            keys[5], (cfg.frontend_dim, cfg.d_model),
            dtype) * cfg.frontend_dim ** -0.5
    return p


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _init_layer_cache(kind: str, cfg, batch: int, cap: int, src_len: int,
                      dtype):
    if kind == "M":
        return ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if kind in ("D", "E", "G", "A"):
        if cfg.mla is not None:
            return attn_mod.init_mla_cache(cfg, batch, cap, dtype)
        return attn_mod.init_attn_cache(cfg, batch, cap, dtype)
    if kind == "L":
        w = min(cap, cfg.sliding_window)
        return attn_mod.init_attn_cache(cfg, batch, w, dtype)
    if kind == "C":
        c = attn_mod.init_attn_cache(cfg, batch, cap, dtype)
        c["xk"] = jnp.zeros((batch, src_len, cfg.n_kv_heads, cfg.head_dim),
                            dtype)
        c["xv"] = jnp.zeros_like(c["xk"])
        return c
    raise ValueError(kind)


def init_cache(cfg, batch: int, cap: int, src_len: int = 0,
               dtype=None, per_slot_len: bool = False) -> dict:
    """``per_slot_len=True`` makes ``cache["len"]`` a (batch,) vector — each
    batch row (decode slot) tracks its own sequence length, the cache layout
    continuous batching decodes against."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    segs = []
    for seg in segments_of(cfg):
        entries = []
        for kind in seg.kinds:
            one = _init_layer_cache(kind, cfg, batch, cap, src_len, dtype)
            entries.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.count,) + x.shape), one))
        segs.append(tuple(entries))
    ln = (jnp.zeros((batch,), jnp.int32) if per_slot_len
          else jnp.zeros((), jnp.int32))
    return {"len": ln, "segments": tuple(segs)}


def merge_cache_slot(cache, sub, slot):
    """Write a batch-1 cache ``sub`` into row ``slot`` of a multi-slot cache.

    Segment cache leaves are stacked (count, batch, ...), so the batch/slot
    dim is axis 1. ``cache["len"]`` must be per-slot (a vector); the slot's
    length is set to ``sub["len"]``. Used by per-slot prefill: a freshly
    prefilled request lands in one decode slot of the shared cache.
    """
    segs = jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_slice_in_dim(
            full, new.astype(full.dtype), slot, axis=1),
        cache["segments"], sub["segments"])
    return {"len": cache["len"].at[slot].set(sub["len"].astype(jnp.int32)),
            "segments": segs}


def slice_cache_slot(cache, slot):
    """Batch-1 copy of row ``slot`` of a multi-slot cache — the inverse view
    of ``merge_cache_slot``. Segment leaves are stacked (count, batch, ...),
    so the slot is sliced on axis 1; the slot's recorded fill level becomes
    the scalar ``len``, so the slice feeds straight into the scalar prefill
    continuation path. ``slot`` may be traced."""
    segs = jax.tree.map(
        lambda full: jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=1),
        cache["segments"])
    return {"len": cache["len"][slot].astype(jnp.int32), "segments": segs}


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_layer(kind, p, x, entry, *, cfg, pc, mode, pos, pos3, length,
                 shared, enc_out=None, collect_stats=False, row_mask=None,
                 layer=None):
    """One layer. Returns (x, new_cache_entry, aux, moe_counts).

    ``layer``: a serving scan's block index; an MoE layer's expert leaves
    then still hold the whole stack (``_run_segment``).

    ``moe_counts`` is None unless ``collect_stats`` and the layer is MoE, in
    which case it is a (B, S, E) float32 per-position count of routed
    (token, k) choices — the live traffic signal harvested by the serving
    monitor (positions kept separate so callers can mask left-padding).

    ``row_mask`` (decode only): (B,) bool gating cache updates per batch
    row — masked-out rows keep their previous KV / latent / SSM state. One
    generic gate here covers every cache layout (GQA, MLA, Mamba, cross-KV).

    Note: no blanket activation constraint here — an explicit per-layer
    P(data, …) pin was tried (§Perf it-3) and REFUTED: neutral for dense
    archs (the FFN/qkv hints do the real work) and actively harmful for
    MoE archs, whose activations want the EP (data, model) token layout
    between layers; pinning them data-only forced per-layer resharding.
    """
    aux = jnp.zeros((), jnp.float32)

    def gate(nc):
        # Freeze masked-out rows' cache state (batch is axis 0 of every
        # cache entry leaf). Elementwise select — stays shard-local.
        if mode != "decode" or row_mask is None or nc is None:
            return nc
        return jax.tree.map(
            lambda new, old: jnp.where(
                row_mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            nc, entry)

    if kind == "M":
        h = rmsnorm(p["ln"], x, cfg.norm_eps)
        if mode == "decode":
            y, nc = ssm_mod.mamba_decode(p["mamba"], h, cfg, entry)
        else:
            # Prefill reads conv/SSD state from the cache entry and writes
            # the final state back, so a chunked continuation (non-zero
            # initial state) is the same code path as a fresh prefill.
            y, nc = ssm_mod.mamba_block(
                p["mamba"], h, cfg, entry if mode == "prefill" else None)
        return x + y, gate(nc), aux, None

    pp = shared if kind == "A" else p
    h = rmsnorm(pp["ln1"], x, cfg.norm_eps)
    window = cfg.sliding_window if kind == "L" else None
    causal = kind != "B"
    block = (partial(attn_mod.mla_block, pc=pc) if cfg.mla is not None
             else partial(attn_mod.attn_block, causal=causal, pc=pc))
    attn_cache = None
    if entry is not None:
        attn_cache = ({k: v for k, v in entry.items()
                       if k not in ("xk", "xv")} if kind == "C" else entry)
    with jax.named_scope("attn"):
        y, nc = block(pp["attn"], h, cfg=cfg, pos=pos, window=window,
                      cache=attn_cache, length=length, mode=mode, pos3=pos3,
                      flash_block=pc.flash_block)
    x = x + y

    if kind == "C":
        hx = rmsnorm(p["ln_x"], x, cfg.norm_eps)
        with jax.named_scope("attn"):
            if mode == "decode":
                kv = {"k": entry["xk"], "v": entry["xv"]}
            else:  # train / prefill: fresh cross K/V from the encoder output
                kv = attn_mod.encode_cross_kv(p["xattn"], enc_out)
            yx = attn_mod.cross_attn_block(p["xattn"], hx, kv, cfg=cfg,
                                           flash_block=pc.flash_block)
        x = x + yx
        if nc is not None:
            nc = dict(nc, xk=kv["k"], xv=kv["v"])

    h2 = rmsnorm(pp["ln2"], x, cfg.norm_eps)
    counts = None
    if kind == "E":
        if collect_stats:
            y2, aux, counts = moe_apply(p["moe"], h2, cfg.moe, cfg.act, pc,
                                        return_counts=True,
                                        layer=layer)          # (B, S, E)
        else:
            y2, aux = moe_apply(p["moe"], h2, cfg.moe, cfg.act, pc,
                                layer=layer)
    else:
        y2 = ffn_apply(pp["ffn"], h2, cfg.act, pc)
    with jax.named_scope("attn/cache_write"):
        nc = gate(nc)
    return x + y2, nc, aux, counts


def _run_segment(seg, seg_params, seg_cache, x, *, cfg, pc, mode, pos, pos3,
                 length, shared, enc_out=None, remat=False,
                 collect_stats=False, row_mask=None):
    """Scan one segment over its ``count`` blocks.

    Returns (x, new_cache, stats, aux). ``stats`` is a tuple with one
    (count, B, S, E) array per MoE kind position when ``collect_stats``,
    else an empty tuple.

    Serving (a cache is present) scans block indices and slices each
    block's weights from the stack itself, under the ``layer_weights``
    scope, so any copies the device makes of them are named in a trace.
    The MoE layers' expert leaves are the exception: they stay whole and
    the block index goes down with them (``moe_apply(layer=)``), so the
    Pallas ``moe_gmm`` reads the layer's blocks from the stack; a slice
    fed to it would be a copy, since XLA cannot fuse a slice into a
    custom call. The scan runs under ``layer_cache``, which names the
    slices of the stacked cache it feeds each block and the stacking of
    the new cache it returns. Training scans the stack as ``xs``: a
    closed-over stack would make the backward pass add a full-size
    cotangent in every iteration."""
    with_cache = mode != "train"

    def block_weights(i):
        with jax.named_scope("layer_weights"):
            return jax.tree_util.tree_map_with_path(
                lambda path, a: a if _is_experts_leaf(path) else
                jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                seg_params)

    def block(carry, xs):
        x, aux = carry
        layer = xs[0] if with_cache else None
        params = block_weights(layer) if with_cache else xs
        cache = xs[1] if with_cache else (None,) * len(seg.kinds)
        new_entries = []
        stats = []
        for i, kind in enumerate(seg.kinds):
            x, nc, a, cnt = _apply_layer(
                kind, params[i], x, cache[i], cfg=cfg, pc=pc, mode=mode,
                pos=pos, pos3=pos3, length=length, shared=shared,
                enc_out=enc_out, collect_stats=collect_stats,
                row_mask=row_mask, layer=layer)
            aux = aux + a
            new_entries.append(nc)
            if cnt is not None:
                stats.append(cnt)
        return (x, aux), (tuple(new_entries) if with_cache else None,
                          tuple(stats))

    if remat:
        block = jax.checkpoint(block)
    xs = ((jnp.arange(seg.count), seg_cache) if with_cache
          else seg_params)
    with (jax.named_scope("layer_cache") if with_cache
          else contextlib.nullcontext()):
        if pc.unroll_segments:
            carry = (x, jnp.zeros((), jnp.float32))
            ys = []
            for b in range(seg.count):
                xs_b = jax.tree.map(lambda t: t[b], xs)
                carry, y = block(carry, xs_b)
                ys.append(y)
            (x, aux) = carry
            new_cache, stats = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
            return x, new_cache if with_cache else None, stats, aux
        (x, aux), (new_cache, stats) = jax.lax.scan(
            block, (x, jnp.zeros((), jnp.float32)), xs, length=seg.count)
    return x, new_cache, stats, aux


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------

def encode(params, cfg, frames, pc: ParallelContext = NO_PARALLEL):
    """Encoder stack (audio): frames (B, S_src, frontend_dim) → (B, S, d)."""
    x = frames @ params["frontend_proj"]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    enc_seg = Segment(("B",), cfg.n_encoder_layers)
    x, _, _, _ = _run_segment(
        enc_seg, params["encoder"]["segments"][0], None, x, cfg=cfg, pc=pc,
        mode="train", pos=pos, pos3=None, length=None, shared=None)
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def forward(params, cfg, *, tokens=None, embeds=None, mode="train",
            cache=None, pc: ParallelContext = NO_PARALLEL, pos3=None,
            enc_out=None, remat=False, collect_moe_stats=False,
            continuation=False, row_mask=None):
    """Run the decoder stack.

    mode "train"/"prefill": tokens (B, S) or embeds (B, S, F). With
    ``continuation=True`` (a STATIC flag) a prefill resumes at the cache's
    fill level ``cache["len"]``: positions and cache writes start at the
    offset and queries attend the cached prefix, so a prompt absorbed in
    chunks is mathematically identical to one-shot prefill. ``len`` may be a
    scalar or a per-slot (B,) vector — each row then resumes at its own
    offset (ring-buffer sliding-window caches support continuation only
    while the prompt fits inside the ring — see
    ``Model.supports_chunked_prefill``). Fresh prefills keep the cheap
    chunk-local attention (O(S^2), not O(S*cap)).
    mode "decode": tokens (B, 1), cache required (reads cache["len"]).
    ``row_mask`` (decode only): (B,) bool; rows where it is False keep their
    cache state and fill level unchanged — the continuous engine freezes
    slots that hold a partially absorbed chunked prefill (their logits are
    still computed and discarded, as for any vacant slot).
    enc_out: encoder output for encoder-decoder archs (train / prefill).
    Returns (logits (B, S, padded_vocab), new_cache | None, aux_loss,
    moe_stats) where moe_stats is a (n_moe_layers, B, S, E) float32 array of
    per-position routed-choice counts (segment-major, kind-major,
    block-major layer order — ``moe_layer_count``) when
    ``collect_moe_stats``, else None. Callers mask pad positions before
    aggregating traffic from prefill stats.
    """
    if cfg.is_encoder_decoder or cfg.input_mode == "text" or embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    else:
        x = embeds @ params["frontend_proj"]
    b, s = x.shape[:2]
    if mode == "decode":
        length = cache["len"]
        if length.ndim == 1:   # per-slot lengths (continuous batching)
            pos = jnp.broadcast_to(length[:, None], (b, s))
        else:
            pos = jnp.broadcast_to(length[None, None], (b, s))
    elif mode == "prefill" and continuation:
        if cache is None:
            raise ValueError("prefill continuation requires a cache")
        length = cache["len"]
        if length.ndim == 1:   # per-slot offsets: each row resumes its own
            pos = length[:, None] + jnp.broadcast_to(jnp.arange(s)[None],
                                                     (b, s))
        else:
            pos = length[None, None] + jnp.broadcast_to(jnp.arange(s)[None],
                                                        (b, s))
    else:
        length = None
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    shared = params.get("shared")
    aux_total = jnp.zeros((), jnp.float32)
    new_segs = []
    stats_parts = []
    for si, seg in enumerate(segments_of(cfg)):
        seg_cache = cache["segments"][si] if cache is not None else None
        x, nc, stats, aux = _run_segment(
            seg, params["segments"][si], seg_cache, x, cfg=cfg, pc=pc,
            mode=mode, pos=pos, pos3=pos3, length=length, shared=shared,
            enc_out=enc_out, remat=remat, collect_stats=collect_moe_stats,
            row_mask=row_mask)
        aux_total = aux_total + aux
        new_segs.append(nc)
        stats_parts.extend(stats)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    with jax.named_scope("lm_head"):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(x.dtype)
    new_cache = None
    if mode != "train" and cache is not None:
        inc = jnp.asarray(s if mode == "prefill" else 1, jnp.int32)
        if mode == "decode" and row_mask is not None:
            inc = inc * row_mask.astype(jnp.int32)   # frozen rows: no bump
        new_cache = {"len": cache["len"] + inc, "segments": tuple(new_segs)}
    moe_stats = None
    if collect_moe_stats:
        moe_stats = (jnp.concatenate(stats_parts, axis=0) if stats_parts
                     else jnp.zeros((0, b, s, 0), jnp.float32))
    return logits, new_cache, aux_total, moe_stats
