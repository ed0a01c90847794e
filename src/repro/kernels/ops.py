"""Jit'd wrappers over the Pallas kernels with automatic fallback.

``interpret`` selects the execution mode everywhere:
- On TPU: compiled Pallas (the production path). Both kernels have been
  compiled for a v5e at phi3.5-moe widths and run on one inside the serving
  path (``chip_smoke.py``).
- On CPU: ``interpret=True`` executes the kernel body in Python for
  correctness validation; ``interpret=None`` (auto) keeps the pure-jnp
  reference so serving and tests stay fast. A compiled program that ran
  the kernels holds ``tpu_custom_call``; ``chip_smoke.py`` checks for it,
  so a run that fell back is not mistaken for a kernel run.

The ``*_auto`` entry points additionally derive legal block shapes from the
runtime array shapes (capacity buckets and cache lengths are workload-sized,
not kernel-sized), so the model layer never has to know the grid rules.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .decode_attn import decode_attn
from .moe_gmm import moe_gmm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(interpret: bool | None = None) -> bool:
    """Whether the kernel path executes a Pallas body (compiled or
    interpret) as opposed to the pure-jnp reference."""
    return on_tpu() or bool(interpret)


def _divisor_block(n: int, block: int, align: int) -> int:
    """Largest block <= ``block`` that divides ``n`` and is a multiple of
    ``align`` (128 on the lane axis, 8 on the sublane axis) — or ``n``
    itself when ``n <= block``, since a whole-axis block is always legal.

    Raises ``ValueError`` where no such block exists: an unaligned block
    passes interpret mode but is refused by the TPU compiler.
    """
    if n <= block:
        return n
    for b in range(block - block % align, 0, -align):
        if n % b == 0:
            return b
    raise ValueError(f"no block of at most {block} divides {n} in multiples "
                     f"of {align}; pad the axis or change the block size")


def moe_ffn(x, w_gate, w_up, w_down, act: str = "swiglu",
            impl: str = "auto", interpret: bool | None = None,
            group_sizes=None, block_c: int = 128, block_f: int = 128,
            layer=None):
    """Grouped expert FFN: Pallas on TPU, reference elsewhere.

    ``group_sizes`` (E,) enables the ragged path: expert blocks past the
    fill level are skipped on the kernel and zero-masked on the reference —
    identical semantics (zero-padded buckets, FFN(0) == 0).
    ``layer``: the weights are a layer stack (L, E, ...) and this traced
    index picks the layer; the kernel reads its blocks from the stack.
    """
    if impl == "ref" or (impl == "auto" and not use_pallas(interpret)):
        return ref.moe_ffn_ref(x, w_gate, w_up, w_down, act,
                               group_sizes=group_sizes, layer=layer)
    return moe_gmm(x, w_gate, w_up, w_down, act=act, layer=layer,
                   group_sizes=group_sizes,
                   block_c=_divisor_block(x.shape[1], block_c, 8),
                   block_f=_divisor_block(w_gate.shape[-1], block_f, 128),
                   interpret=bool(interpret) if interpret is not None
                   else not on_tpu())


def decode_attn_auto(q, k, v, valid_len, block_s: int = 512,
                     interpret: bool | None = None):
    """Decode-step attention over a per-slot cache, impl auto-selected.

    q: (B, H, D); k/v: (B, S, Hkv, D); valid_len scalar or (B,) fill levels
    (broadcast to every batch row). Picks the largest sublane-aligned KV
    block that divides the cache capacity (``_divisor_block``).
    """
    b = q.shape[0]
    valid_len = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))
    if not use_pallas(interpret):
        return ref.decode_attn_ref(q, k, v, valid_len)
    return decode_attn(q, k, v, valid_len,
                       block_s=_divisor_block(k.shape[1], block_s, 8),
                       interpret=bool(interpret) if interpret is not None
                       else not on_tpu())
