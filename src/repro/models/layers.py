"""Shared neural building blocks (pure-function JAX, no framework deps).

Everything here is dtype- and sharding-polymorphic: params are plain nested
dicts of ``jnp.ndarray``; an optional ``ParallelContext`` adds
``with_sharding_constraint`` hints (no-ops on a single device).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Pallas kernel-path settings for the serving hot path.

    Attaching one to ``ParallelContext.kernels`` (see ``Model.with_kernels``)
    routes decode-step attention through ``kernels.ops.decode_attn_auto`` and
    — together with ``moe_impl="kernel"`` — MoE dispatch through the
    sort-based ragged path feeding ``kernels.moe_gmm``.

    ``interpret``: None = auto (compiled Pallas on TPU, pure-jnp reference on
    CPU); True forces Pallas interpret mode (correctness validation on CPU).

    The blocks are upper bounds: ``kernels.ops`` takes the largest aligned
    divisor of the runtime axis. These defaults compile for a TPU v5e at
    phi3.5-moe widths (``tests/test_tpu_compile.py``); ``block_s=512`` keeps
    a (512, 8·128) bf16 key/value block pair, double buffered, at 4 MiB of
    the 16 MiB scoped VMEM.
    """

    interpret: bool | None = None
    block_c: int = 128    # moe_gmm capacity-row block
    block_f: int = 128    # moe_gmm d_ff block (reduction axis)
    block_s: int = 512    # decode_attn KV-sequence block


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """How the model is laid out on a mesh.

    ``data_axes``: mesh axes the batch is sharded over (e.g. ("pod","data")).
    ``model_axis``: mesh axis for tensor parallelism (heads / d_ff / vocab).
    ``ep_axes``: mesh axes forming the flat expert-parallel axis for MoE
    dispatch (None → dense reference dispatch).
    ``seq_axis``: axis to shard long KV caches' sequence dim over (used when
    batch is too small to shard, e.g. long_500k).
    """

    mesh: Any = None
    data_axes: tuple[str, ...] = ()
    model_axis: str | None = None
    ep_axes: tuple[str, ...] | None = None   # collective axes for MoE a2a
    token_axes: tuple[str, ...] = ()         # all axes the flat token dim
    #                                          shards over (pod stays outside
    #                                          the EP collective: no all-to-all
    #                                          ever crosses the DCN boundary)
    seq_axis: str | None = None
    aurora_rounds: tuple[tuple[int, ...], ...] | None = None  # ppermute schedule
    ep_overlap: bool = False  # round-pipelined dispatch: expert FFN chunks
    #                           overlap in-flight ppermute rounds
    #                           (repro.distributed.overlap)
    moe_impl: str = "dense"  # dense | ep | aurora | kernel
    kernels: KernelConfig | None = None      # non-None → kernelized hot path
    moe_replication: Any = None  # moe.ReplicationSpec | None: hot-expert
    #                              replicas (params widened to sum(counts)
    #                              physical experts; routing stays logical)
    flash_block: int = 1024
    unroll_segments: bool = False  # Python-loop layer blocks instead of
    #                                lax.scan (cost-calibration lowerings:
    #                                XLA counts a while body ONCE regardless
    #                                of trip count)

    def shard(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))


NO_PARALLEL = ParallelContext()


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(w, x, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); pos: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = pos[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, pos3: jnp.ndarray, theta: float,
                sections: tuple[int, int, int]) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE. pos3: (3, ..., S) temporal/height/width ids.

    The head_dim/2 frequency slots are split into three sections, each
    rotated by its own position stream (all three equal for pure text).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    # Build per-slot position by section.
    sec = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)
    ])                                                 # (D/2,) in {0,1,2}
    pos_sel = jnp.moveaxis(pos3, 0, -1)                # (..., S, 3)
    pos_per_slot = jnp.take(pos_sel, sec, axis=-1)     # (..., S, D/2)
    angles = pos_per_slot.astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def plain_attention(q, k, v, mask) -> jnp.ndarray:
    """GQA attention without repeating KV.

    q: (B,Sq,Hkv,G,D); k,v: (B,Sk,Hkv,D); mask: (1|B,1,Sq,Sk) bool or None.
    Keeping the kv-head/group split as separate einsum dims (instead of
    broadcast+reshape repeat_kv) avoids 4× KV temporaries AND a GSPMD
    "involuntary full rematerialization" of seq-sharded caches at decode
    (§Perf iteration 6).
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention(q, k, v, mask_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] | None,
                    block_k: int = 1024) -> jnp.ndarray:
    """Memory-bounded GQA attention: scan over KV blocks, online softmax.

    q: (B,Sq,Hkv,G,D); k,v: (B,Sk,Hkv,D). Never materializes the (Sq, Sk)
    score matrix — peak temporary is (B, Hkv, G, Sq, block_k).
    ``mask_fn(q_pos, k_pos) -> bool`` builds the mask for one block
    (causal / sliding window / cache-length).
    """
    b, sq, hkv, g, d = q.shape
    sk = k.shape[1]
    nb = -(-sk // block_k)
    pad = nb * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    scale = d ** -0.5
    q_pos = jnp.arange(sq)

    def step(carry, inp):
        m, l, acc = carry
        blk_idx, kblk, vblk = inp
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = k_pos < sk
        if mask_fn is not None:
            valid = valid[None, :] & mask_fn(q_pos[:, None], k_pos[None, :])
        else:
            valid = jnp.broadcast_to(valid[None, :], (sq, block_k))
        s = jnp.where(valid[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,Hkv,G,Sq,D)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def attention_core(q, k, v, *, causal_offset: jnp.ndarray | int | None,
                   window: int | None, valid_len: jnp.ndarray | None,
                   flash_block: int = 1024) -> jnp.ndarray:
    """Dispatch between plain and flash attention (GQA-native, no repeat).

    q: (B,Sq,H,D); k,v: (B,Sk,Hkv,D) with H = Hkv·G.
    ``causal_offset``: query i may attend key j iff j <= i + offset
    (offset = Sk - Sq for self-attention with a prefix cache; None = no
    causal mask, e.g. encoder self-attention / cross-attention).
    ``window``: additionally require j > i + offset - window.
    ``valid_len``: keys >= valid_len are masked (cache fill level). Both
    ``causal_offset`` and ``valid_len`` may be scalars (one value for the
    whole batch) or (B,) vectors (per-slot values — continuous batching /
    batched chunked continuation).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]

    def mask_fn(qi, kj):
        m = jnp.ones(jnp.broadcast_shapes(qi.shape, kj.shape), bool)
        if causal_offset is not None:
            m &= kj <= qi + causal_offset
            if window is not None:
                m &= kj > qi + causal_offset - window
        if valid_len is not None:
            m &= kj < valid_len
        return m

    # Mode split (§Perf it-6): at DECODE (single query over a seq-sharded
    # cache) the grouped form avoids repeat_kv's broadcast+reshape, which
    # GSPMD can only realize by fully rematerializing the cache. At
    # train/prefill the grouped 5-D reshape would instead SPLIT the
    # model-sharded head dim (Hkv < axis size), so the classic repeated-KV
    # form partitions better there.
    if sq == 1:
        qg = q.reshape(b, sq, hkv, h // hkv, d)
        if valid_len is None:
            mask = None
        elif jnp.ndim(valid_len) == 1:
            # Per-slot fill levels: (B, 1, Sq, Sk) mask, one row per slot.
            mask = (jnp.arange(sk)[None, None, None, :]
                    < valid_len[:, None, None, None])
        else:
            mask = mask_fn(jnp.arange(sq)[:, None],
                           jnp.arange(sk)[None, :])[None, None]
        out = plain_attention(qg, k, v, mask)
        return out.reshape(b, sq, h, d)

    if ((causal_offset is not None and jnp.ndim(causal_offset) == 1)
            or (valid_len is not None and jnp.ndim(valid_len) == 1)):
        # Per-row offsets / fill levels at Sq > 1: a batch of chunked
        # prefill continuations, each resuming at its own cache offset.
        # Chunks are short, so the (B, Sq, Sk) mask is materialized and the
        # grouped plain form used directly — no flash.
        qi = jnp.arange(sq)[None, :, None]
        kj = jnp.arange(sk)[None, None, :]
        m = jnp.ones((b, sq, sk), bool)
        if causal_offset is not None:
            off = jnp.reshape(jnp.asarray(causal_offset), (-1, 1, 1))
            m &= kj <= qi + off
            if window is not None:
                m &= kj > qi + off - window
        if valid_len is not None:
            m &= kj < jnp.reshape(jnp.asarray(valid_len), (-1, 1, 1))
        out = plain_attention(q.reshape(b, sq, hkv, h // hkv, d), k, v,
                              m[:, None])
        return out.reshape(b, sq, h, d)

    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    qg = q[:, :, :, None, :]                      # (B,Sq,H,1,D): G=1 form
    if sq * sk <= 4_194_304:  # small enough to materialize scores
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(sk)[None, :]
        need_mask = causal_offset is not None or valid_len is not None
        mask = mask_fn(qi, kj)[None, None] if need_mask else None
        out = plain_attention(qg, k, v, mask)
    else:
        out = flash_attention(qg, k, v, mask_fn, block_k=flash_block)
    return out.reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# FFN (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def ffn_apply(p, x, act: str, pc: ParallelContext = NO_PARALLEL):
    h_gate = x @ p["w_gate"]
    h_up = x @ p["w_up"]
    # Column-parallel hint: batch over the data axes, d_ff over the model
    # axis. (A PartitionSpec ``None`` means REPLICATED, not unconstrained —
    # omitting the batch axes here forced GSPMD to all-gather the full
    # global batch before every FFN dot; §Perf iteration 3.) Applied only
    # to (B, S, d) activations: 2-D (tokens, d) inputs — the MoE shared
    # expert — carry a flat token sharding that a None spec would destroy.
    if (pc.mesh is not None and pc.model_axis is not None and x.ndim == 3
            and h_gate.shape[-1] % pc.mesh.shape[pc.model_axis] == 0):
        nb = 1
        for a in pc.data_axes:
            nb *= pc.mesh.shape[a]
        batch_ax = pc.data_axes if (nb and x.shape[0] % nb == 0) else None
        spec = (batch_ax, None, pc.model_axis)
        h_gate = pc.shard(h_gate, *spec)
        h_up = pc.shard(h_up, *spec)
    act_fn = jax.nn.gelu if act == "geglu" else jax.nn.silu
    h = act_fn(h_gate) * h_up
    return h @ p["w_down"]


def init_ffn(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "w_gate": jax.random.normal(k1, (d_model, d_ff), dtype) * s_in,
        "w_up": jax.random.normal(k2, (d_model, d_ff), dtype) * s_in,
        "w_down": jax.random.normal(k3, (d_ff, d_model), dtype) * s_out,
    }
