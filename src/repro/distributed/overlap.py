"""Round-pipelined (overlapped) Aurora dispatch — the paper's Fig 3(b) at
intra-step granularity.

The synchronous EP path (``alltoall._local_dispatch_combine``) is a strict
barrier pipeline: *all* ppermute rounds of the dispatch all-to-all complete,
then the expert FFN runs over every arrival, then *all* return rounds fire.
Lina and FasterMoE (PAPERS.md) show the win comes from breaking that barrier:
expert compute on tokens that already arrived can hide the latency of rounds
still in flight.

``pipelined_local_dispatch_combine`` realizes this as a **software pipeline**
over the BvN rounds:

  round r+1's ppermute is issued          ─┐  data-independent, so XLA's
  FFN runs on the chunk from round r       ├─ latency-hiding scheduler
  round r's output returns (ppermuteᵀ)    ─┘  overlaps all three

Each round delivers at most one (experts_per_device, C, d) capacity chunk
per device; the grouped expert FFN is applied per chunk (FFN is row-wise, so
per-chunk compute equals the batched compute on the concatenation), and the
finished chunk returns through the **transposed** permutation of its delivery
round — still a (partial) permutation, so the return phase keeps the paper's
contention-free invariant.

Token-identity with the synchronous path is proven in
``tests/test_distributed_serving.py``: same routing, same capacity buckets,
same gate-weighted combine — only the schedule of byte movement changes.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from .alltoall import _replicated_counts, _scatter_buckets, flat_axis_index


def pipelined_local_dispatch_combine(xt, valid, router_w, experts, moe, act,
                                     ep_axes, token_axes, rounds,
                                     return_counts: bool = False, spec=None):
    """Per-device body of the round-pipelined dispatch/FFN/combine.

    Same contract as ``alltoall._local_dispatch_combine`` (and proven
    token-identical to it): xt (T_loc, d) local token slice in, combined
    expert outputs out. ``rounds`` must be an explicit ppermute schedule —
    the pipeline has no monolithic-all_to_all fallback.
    """
    from repro.models.layers import ffn_apply

    if rounds is None:
        raise ValueError("the pipelined dispatch needs explicit ppermute "
                         "rounds (aurora_rounds or round_robin_rounds)")
    t_loc, d = xt.shape
    n_ep = 1
    for ax in ep_axes:
        n_ep *= jax.lax.axis_size(ax)
    e = moe.n_experts
    axis_name = tuple(ep_axes) if len(ep_axes) > 1 else ep_axes[0]
    me = flat_axis_index(ep_axes)

    buf, combine, aux, idx = _scatter_buckets(xt, valid, router_w, moe,
                                              token_axes, spec=spec)
    n_phys, cap = buf.shape[0], buf.shape[1]
    epd = n_phys // n_ep                             # experts per device
    buf = buf.reshape(n_ep, epd, cap, d)             # buf[s] → device s

    def experts_ffn(chunk):                          # (epd, C, d)
        with jax.named_scope("moe/experts"):
            return jax.vmap(lambda p, xb: ffn_apply(p, xb, act))(experts,
                                                                  chunk)

    # out[s] = FFN outputs of MY tokens processed on device s's experts;
    # row n_ep is a scratch slot for rounds where this device is idle.
    out = jnp.zeros((n_ep + 1, epd, cap, d), xt.dtype)

    def flush(out, chunk, back_perm, write_tbl):
        """Drain one arrived chunk: grouped FFN, then return it through the
        transposed permutation of its delivery round (local for the self
        chunk). Issued AFTER the next round's forward ppermute, so both the
        FFN and the return transfer sit in that round's latency window."""
        y = experts_ffn(chunk)
        with jax.named_scope("moe/exchange"):
            if back_perm is None:                    # self chunk: no network
                return jax.lax.dynamic_update_index_in_dim(out, y, me, 0)
            back = jax.lax.ppermute(y, axis_name, back_perm)
            w = jnp.asarray(write_tbl)[me]
            return jax.lax.dynamic_update_index_in_dim(out, back, w, 0)

    # Prologue: the self chunk "arrived" before any round; its FFN fills the
    # first round's latency window (self-traffic never crosses the network).
    pending = (jax.lax.dynamic_index_in_dim(buf, me, 0, keepdims=False),
               None, None)
    for dst_vec in rounds:
        dst = np.asarray(dst_vec)
        perm = [(i, int(j)) for i, j in enumerate(dst) if j >= 0]
        with jax.named_scope("moe/exchange"):
            send_idx = jnp.asarray(np.where(dst < 0, 0, dst))[me]
            send = jax.lax.dynamic_index_in_dim(buf, send_idx, 0,
                                                keepdims=False)
            recv = jax.lax.ppermute(send, axis_name, perm)  # in flight…
        out = flush(out, *pending)                       # …compute ≤ r
        # The chunk just received returns through the transposed permutation
        # and lands in my out row for the device I sent to this round.
        pending = (recv, [(j, i) for (i, j) in perm],
                   np.where(dst < 0, n_ep, dst))
    out = flush(out, *pending)                           # pipeline epilogue

    back = out[:n_ep].reshape(n_phys, cap, d)
    y = combine(back)
    if return_counts:
        return y, aux, _replicated_counts(idx, valid, e, token_axes)
    return y, aux


def pipelined_dispatch_combine(xt, router_w, experts, moe, act, pc,
                               return_counts: bool = False):
    """``ep_dispatch_combine`` with the software pipeline forced on,
    regardless of ``pc.ep_overlap`` / ``pc.moe_impl``.

    Exists so callers (tests, benchmarks) can compare the two paths on one
    ``ParallelContext``; the serving engines flip ``pc.ep_overlap`` instead.
    Delegates to the one shard_map wrapper (token padding, specs, and the
    round-robin fallback live in exactly one place).
    """
    from .alltoall import ep_dispatch_combine

    pc = dataclasses.replace(pc, moe_impl="aurora", ep_overlap=True)
    return ep_dispatch_combine(xt, router_w, experts, moe, act, pc,
                               return_counts=return_counts)
