"""Pure-jnp oracles for the Pallas kernels (tested via assert_allclose)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_ffn_ref(x, w_gate, w_up, w_down, act: str = "swiglu",
                group_sizes=None, layer=None):
    """Grouped expert FFN over capacity buckets.

    x: (E, C, d); w_gate/w_up: (E, d, f); w_down: (E, f, d) → (E, C, d).
    ``group_sizes``: optional (E,) real-row counts — rows at or beyond a
    group's fill level are zeroed, matching the ragged kernel's block-skip
    semantics exactly (pad rows are zero inputs, and FFN(0) == 0).
    ``layer``: the weights are a layer stack (L, E, ...); use layer
    ``layer`` of it (the slice fuses into the einsums that read it).
    """
    if layer is not None:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
            for w in (w_gate, w_up, w_down))
    act_fn = jax.nn.gelu if act == "geglu" else jax.nn.silu
    h = act_fn(jnp.einsum("ecd,edf->ecf", x, w_gate,
                          preferred_element_type=jnp.float32))
    h = h * jnp.einsum("ecd,edf->ecf", x, w_up,
                       preferred_element_type=jnp.float32)
    y = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)
    if group_sizes is not None:
        live = jnp.arange(x.shape[1])[None, :] < group_sizes[:, None]
        y = jnp.where(live[..., None], y, 0.0)
    return y.astype(x.dtype)


def decode_attn_ref(q, k, v, valid_len):
    """Single-query GQA flash-decode oracle.

    q: (B, H, D); k/v: (B, S, Hkv, D); valid_len: (B,) int32 → (B, H, D).
    """
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    mask = jnp.arange(s)[None] < valid_len[:, None]          # (B, S)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d).astype(q.dtype)
