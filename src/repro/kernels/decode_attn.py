"""Pallas TPU kernel: single-query flash-decode attention.

The decode shapes (``decode_32k``, ``long_500k``) are dominated by streaming
the KV cache past one query token — a pure memory-bandwidth problem. The
kernel tiles the cache into (block_s, Hkv·D) VMEM blocks and maintains an
online-softmax running (max, sum, accumulator) across sequence blocks, so
the (S)-long score row is never materialized in HBM and each cache byte is
read exactly once.

TPU mapping: grid (B, S/block_s) with the sequence axis innermost
(arbitrary = sequential accumulation). GQA is handled in-block: q is viewed
as (Hkv, G, D) and each kv head's keys and values are a D-wide column
slice of the block, fed to the MXU in the cache dtype (lane-aligned when D
is a multiple of 128). The whole (B,) ``valid_len`` array rides in SMEM,
indexed by the batch grid index, and masks cache slots beyond each row's
fill level.

Validated against ``ref.decode_attn_ref`` in interpret mode, compiled for a
described TPU v5e at phi3.5-moe decode shapes (B=8, H=32, Hkv=8, D=128,
S=2048, block_s=512: ``tests/test_tpu_compile.py``), and run on a v5e chip
inside the phi3.5-moe serving path (``chip_smoke.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, n_s: int, scale: float):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hkv, g, d = q_ref.shape[1:]
    valid = (s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (g, block_s), 1)) < vl_ref[pl.program_id(0)]

    # One kv head at a time: its (bs, D) key/value block is a lane-aligned
    # column slice of the (bs, Hkv*D) cache block, and both matmuls take the
    # cache dtype straight into the MXU with f32 accumulation — no f32 copy
    # or transpose of the block, so VMEM holds little beyond the DMA buffers.
    for j in range(hkv):
        q = q_ref[0, j]                              # (G, D)
        k = k_ref[0, :, j * d:(j + 1) * d]           # (bs, D)
        v = v_ref[0, :, j * d:(j + 1) * d]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bs)
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev = m_ref[j]                            # (G, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)                  # (G, bs)
        corr = jnp.exp(m_prev - m_new)               # (G, 1)
        l_ref[j] = l_ref[j] * corr + p.sum(axis=-1, keepdims=True)
        ctx = jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)  # (G, D)
        acc_ref[j] = acc_ref[j] * corr + ctx
        m_ref[j] = m_new

    @pl.when(s_idx == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attn(q, k, v, valid_len, *, block_s: int = 512,
                interpret: bool = False):
    """Flash-decode. q: (B, H, D); k/v: (B, S, Hkv, D); valid_len: (B,)."""
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bs = min(block_s, s)
    if s % bs:
        raise ValueError(f"S={s} not divisible by block_s={bs}")
    n_s = s // bs
    # Free reshapes: GQA groups on their own axis, heads folded into lanes.
    qg = q.reshape(b, hkv, g, d)
    k = k.reshape(b, s, hkv * d)
    v = v.reshape(b, s, hkv * d)
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, n_s=n_s, scale=d ** -0.5),
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole (B,) array
            pl.BlockSpec((1, hkv, g, d), lambda b_, s_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv * d), lambda b_, s_: (b_, s_, 0)),
            pl.BlockSpec((1, bs, hkv * d), lambda b_, s_: (b_, s_, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), lambda b_, s_: (b_, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # running max
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # running sum
            pltpu.VMEM((hkv, g, d), jnp.float32),    # context accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(valid_len, qg, k, v)
    return out.reshape(b, h, d)
