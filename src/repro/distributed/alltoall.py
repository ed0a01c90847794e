"""Expert-parallel dispatch/combine collectives.

The MoE all-to-all is realized two ways:

1. **Baseline** — one monolithic ``jax.lax.all_to_all`` per phase. This is
   what existing systems (GShard / DeepSpeed-MoE / Tutel) lower to and what
   the paper's baselines model: the runtime picks an arbitrary transmission
   order, so receivers can suffer bandwidth contention.

2. **Aurora** — the paper's Thm 4.2 schedule: a static sequence of
   ``lax.ppermute`` **permutation rounds**. Each round is a (partial)
   permutation of the devices, so every device sends to at most one peer and
   receives from at most one peer — exactly the paper's contention-free
   invariant, and also the contention-free traffic pattern for the TPU ICI
   torus. The round order is computed host-side by ``repro.core.schedule``
   from historical traffic statistics (the paper's §2.4 prerequisite) and
   baked into the compiled program ("a buffer layer … calls communication
   collective libraries in the desired order", §3).

Both variants move identical bytes; on real hardware the Aurora variant
avoids receiver contention for skewed traffic. On the dry-run we verify both
lower/compile and that the HLO shows the expected collective structure.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Round construction (host side)
# ---------------------------------------------------------------------------

def round_robin_rounds(n: int) -> tuple[tuple[int, ...], ...]:
    """Default contention-free cover: n-1 cyclic-shift permutations.

    Round r sends i → (i + r) mod n. Every ordered pair appears exactly once
    and every round is a full permutation — the unscheduled (traffic-blind)
    member of the family Aurora optimizes over.
    """
    return tuple(
        tuple((i + r) % n for i in range(n)) for r in range(1, n)
    )


def aurora_rounds_from_schedule(schedule, n: int) -> tuple[tuple[int, ...], ...]:
    """Collapse a ``CommSchedule`` into one exchange round per (src, dst) pair.

    The BvN schedule may split a pair across slots (durations differ); the
    static lowering moves each pair's whole capacity bucket in the slot where
    the pair FIRST appears — preserving Aurora's *ordering* decision (heavy
    pairs early, contention-free rounds). Pairs absent from the schedule
    (zero historical traffic) are appended as round-robin cleanup rounds so
    the exchange stays correct under traffic drift (§8 Q4).

    Degenerate inputs are handled explicitly: a single device needs no
    rounds (self-traffic never crosses the network), and malformed slots
    (duplicate receivers, self-sends, out-of-range destinations) raise
    instead of silently misrouting buckets in the ppermute lowering.
    """
    from repro.core.schedule import validate_permutation_slots

    validate_permutation_slots(schedule.slots, n)
    if n == 1:
        return ()
    seen = np.zeros((n, n), dtype=bool)
    rounds: list[tuple[int, ...]] = []
    for slot in schedule.slots:
        dst = []
        any_new = False
        for i, j in enumerate(slot.dst):
            if j >= 0 and not seen[i, j]:
                seen[i, j] = True
                dst.append(j)
                any_new = True
            else:
                dst.append(-1)
        if any_new:
            rounds.append(tuple(dst))
    # Cleanup: cover never-seen off-diagonal pairs with round-robin shifts.
    for r in range(1, n):
        dst = []
        any_new = False
        for i in range(n):
            j = (i + r) % n
            if not seen[i, j]:
                seen[i, j] = True
                dst.append(j)
                any_new = True
            else:
                dst.append(-1)
        if any_new:
            rounds.append(tuple(dst))
    return tuple(rounds)


def validate_rounds_cover(rounds, n: int) -> tuple[tuple[int, ...], ...]:
    """Demand a full contention-free cover from a literal round sequence.

    The exchange bodies trust ``rounds`` blindly: a missing (src, dst) pair
    leaves that capacity bucket's row as zeros (tokens silently vanish), a
    duplicate delivers one bucket twice. Everything derived through
    ``aurora_rounds_from_schedule`` satisfies this by construction; rounds
    installed verbatim (``swap_rounds`` / engine ``rounds=``) go through
    here so misuse fails loudly instead. Returns the normalized tuple.
    """
    from repro.core.schedule import check_partial_permutation

    rounds = tuple(check_partial_permutation(r, n, f"round {r_i}")
                   for r_i, r in enumerate(rounds))
    seen = np.zeros((n, n), dtype=int)
    for dst in rounds:
        for i, j in enumerate(dst):
            if j >= 0:
                seen[i, j] += 1
    off = ~np.eye(n, dtype=bool)
    if n > 1 and not (seen[off] == 1).all():
        missing = int((seen[off] == 0).sum())
        dup = int((seen[off] > 1).sum())
        raise ValueError(
            f"rounds are not an exact cover of the {n}-device exchange: "
            f"{missing} ordered pair(s) never exchanged (their token "
            f"buckets would silently vanish), {dup} exchanged more than "
            "once")
    return rounds


# ---------------------------------------------------------------------------
# In-shard_map exchange primitives
# ---------------------------------------------------------------------------

def flat_axis_index(axis_names):
    """Row-major flattened device index over ``axis_names`` (traced)."""
    me = jnp.zeros((), jnp.int32)
    for ax in axis_names:
        me = me * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return me


def _exchange_rounds(buf, axis_names, rounds) -> jnp.ndarray:
    """Scheduled exchange: buf (n, ...) slices; out[s] = buf_of_device_s[me].

    Equivalent to ``lax.all_to_all(buf, axes, 0, 0)`` but expressed as the
    static ppermute round sequence (each round a partial permutation).
    Multi-axis EP (e.g. deepseek's flat ('data','model') = 256) uses the
    row-major flattened device index, matching all_to_all's ordering.
    """
    n = buf.shape[0]
    me = flat_axis_index(axis_names)
    axis_name = tuple(axis_names) if len(axis_names) > 1 else axis_names[0]
    # Row n is a scratch slot for rounds in which this device receives nothing.
    out = jnp.zeros((n + 1,) + buf.shape[1:], buf.dtype)
    for dst_vec in rounds:
        dst = np.asarray(dst_vec)
        src = np.full(n, n, dtype=np.int64)          # n = scratch
        for i, j in enumerate(dst):
            if j >= 0:
                src[j] = i
        perm = [(i, int(j)) for i, j in enumerate(dst) if j >= 0]
        send_idx = jnp.asarray(np.where(dst < 0, 0, dst))[me]
        send = jax.lax.dynamic_index_in_dim(buf, send_idx, 0, keepdims=False)
        recv = jax.lax.ppermute(send, axis_name, perm)
        write_idx = jnp.asarray(src)[me]
        out = jax.lax.dynamic_update_index_in_dim(out, recv, write_idx, 0)
    # Self-traffic never crosses the network (paper §4.2 footnote 1).
    out = jax.lax.dynamic_update_index_in_dim(
        out, jax.lax.dynamic_index_in_dim(buf, me, 0, keepdims=False), me, 0)
    return out[:n]


def ep_all_to_all(buf, axis_names, rounds=None) -> jnp.ndarray:
    """Dispatch exchange over the flat EP axis. buf: (n_ep, ...) per device.

    Result[s] = what device s sent to me. ``rounds=None`` → monolithic
    all_to_all; otherwise the Aurora ppermute schedule (works for single-
    and multi-axis flat EP).
    """
    if rounds is not None:
        return _exchange_rounds(buf, tuple(axis_names), rounds)
    return jax.lax.all_to_all(buf, axis_names, split_axis=0, concat_axis=0,
                              tiled=False)


# ---------------------------------------------------------------------------
# Full dispatch → expert FFN → combine (runs inside shard_map)
# ---------------------------------------------------------------------------

def _scatter_buckets(xt, valid, router_w, moe, token_axes, spec=None):
    """Shared dispatch prologue of the sync and pipelined bodies.

    Routes the local token slice and scatters it into per-expert capacity
    buckets. Returns ``(buf (E', C, d), combine, aux, idx)`` where ``combine``
    maps the returned (E', C, d) expert-output buckets back onto the local
    token slice (gate-weighted scatter-add).

    ``spec`` (a ``moe.ReplicationSpec``) widens the bucket frame to the
    physical expert count: routing/capacity/drops stay in the LOGICAL frame
    (bit-identical to no replication), then kept rank r of expert e lands on
    replica ``r % r_e`` at position ``r // r_e`` — the same shard-of-token
    rule as the local paths, so replicas are placement-only."""
    from repro.models.moe import capacity, dispatch_indices, replica_arrays, \
        route

    t_loc, d = xt.shape
    e = moe.n_experts
    with jax.named_scope("moe/router"):
        gates, idx, aux = route(router_w, xt, moe)
        aux = jax.lax.pmean(aux, token_axes)
    with jax.named_scope("moe/dispatch"):
        cap = capacity(t_loc, moe.top_k, e, moe.capacity_factor)
        slot, keep = dispatch_indices(idx, e, cap)
        keep = keep & valid[:, None]
        # Scatter local tokens into per-(expert) capacity buckets.
        tok_ids = jnp.broadcast_to(jnp.arange(t_loc)[:, None], idx.shape)
        e_f, s_f, t_f = idx.reshape(-1), slot.reshape(-1), tok_ids.reshape(-1)
        k_f = keep.reshape(-1)
        if spec is not None:
            base, reps = replica_arrays(spec)
            r_f = reps[e_f]
            e_f = base[e_f] + s_f % r_f
            s_f = s_f // r_f
            n_phys = spec.n_phys
        else:
            n_phys = e
        safe_s = jnp.where(k_f, s_f, cap - 1)
        buf = jnp.zeros((n_phys, cap, d), xt.dtype)
        buf = buf.at[e_f, safe_s].add(jnp.where(k_f[:, None], xt[t_f], 0.0))

    def combine(back):
        with jax.named_scope("moe/combine"):
            picked = back[e_f, safe_s]
            picked = jnp.where(k_f[:, None], picked, 0.0)
            return jnp.zeros_like(xt).at[t_f].add(
                picked * gates.reshape(-1)[:, None])

    return buf, combine, aux, idx


def _replicated_counts(idx, valid, n_experts: int, token_axes):
    """In-collective ``return_counts``: per-token routed-choice histogram.

    Routing runs inside the shard_map collective, so per-token assignments
    never materialize outside the per-device program — each device scatters
    its local (T_loc, E) ``routed_counts`` slice into the global padded token
    range and a ``psum`` over the token axes replicates the full (T_pad, E)
    histogram, exactly matching the local paths' output frame."""
    from repro.models.moe import routed_counts

    with jax.named_scope("moe/router"):
        cnt = routed_counts(idx, n_experts) * valid[:, None].astype(
            jnp.float32)
        t_loc = cnt.shape[0]
        n_shards = 1
        for ax in token_axes:
            n_shards *= jax.lax.axis_size(ax)
        shard = flat_axis_index(token_axes)
        full = jnp.zeros((n_shards * t_loc, n_experts), jnp.float32)
        full = jax.lax.dynamic_update_slice(full, cnt, (shard * t_loc, 0))
        return jax.lax.psum(full, tuple(token_axes))


def _local_dispatch_combine(xt, valid, router_w, experts, moe, act,
                            ep_axes, token_axes, rounds,
                            return_counts: bool = False, spec=None):
    """Per-device body (synchronous). xt: (T_loc, d) local token slice."""
    t_loc, d = xt.shape
    n_ep = 1
    for ax in ep_axes:
        n_ep *= jax.lax.axis_size(ax)
    e = moe.n_experts

    buf, combine, aux, idx = _scatter_buckets(xt, valid, router_w, moe,
                                              token_axes, spec=spec)
    n_phys, cap = buf.shape[0], buf.shape[1]
    epd = n_phys // n_ep                             # experts per device

    # First all-to-all (token dispatch, D_N).
    with jax.named_scope("moe/exchange"):
        buf = buf.reshape(n_ep, epd, cap, d)
        recv = ep_all_to_all(buf, ep_axes, rounds)   # (n_src, epd, C, d)
        recv = recv.transpose(1, 0, 2, 3).reshape(epd, n_ep * cap, d)

    # Expert FFN on this device's experts.
    from repro.models.layers import ffn_apply
    with jax.named_scope("moe/experts"):
        out = jax.vmap(lambda p, xb: ffn_apply(p, xb, act))(experts, recv)

    # Second all-to-all (expert-output return, D_C = D_N^T): same rounds —
    # the two phases are exact reverses (§2.2), so the contention-free
    # property carries over by symmetry.
    with jax.named_scope("moe/exchange"):
        out = out.reshape(epd, n_ep, cap, d).transpose(1, 0, 2, 3)
        back = ep_all_to_all(out, ep_axes, rounds)   # (E_dev_of_pair …)
        back = back.reshape(n_phys, cap, d)

    y = combine(back)
    if return_counts:
        return y, aux, _replicated_counts(idx, valid, e, token_axes)
    return y, aux


def ep_dispatch_combine(xt, router_w, experts, moe, act, pc,
                        return_counts: bool = False):
    """shard_map wrapper. xt: (T, d) global.

    The flat token axis shards over ``pc.token_axes`` (all mesh axes —
    including ``pod``); the all-to-all collectives run over ``pc.ep_axes``
    only, so each pod performs its own expert exchange and **no all-to-all
    crosses the DCN boundary** (DESIGN.md §6). Pads T to a multiple of the
    token-shard count (decode steps can have fewer tokens than devices);
    padded tokens are masked out of dispatch.

    ``pc.ep_overlap=True`` switches the body to the round-pipelined software
    pipeline (``repro.distributed.overlap``): expert FFN chunks run while the
    next ppermute round is in flight. ``return_counts=True`` appends the
    (T, E) routed-choice histogram, psum'd inside the collective.
    """
    ep_axes = tuple(pc.ep_axes)
    token_axes = tuple(pc.token_axes) or ep_axes
    mesh = pc.mesh
    n_tok_shards = 1
    for ax in token_axes:
        n_tok_shards *= mesh.shape[ax]
    t = xt.shape[0]
    t_pad = -(-t // n_tok_shards) * n_tok_shards
    valid = jnp.arange(t_pad) < t
    if t_pad != t:
        xt = jnp.pad(xt, ((0, t_pad - t), (0, 0)))

    n_ep = 1
    for ax in ep_axes:
        n_ep *= mesh.shape[ax]
    spec = pc.moe_replication
    if spec is not None and spec.n_phys % n_ep != 0:
        raise ValueError(
            f"replicated physical expert count {spec.n_phys} does not "
            f"divide over the {n_ep}-device EP axis — pad the replication "
            f"(planner: total_multiple={n_ep}) so every device hosts the "
            "same number of physical experts")
    rounds = pc.aurora_rounds if pc.moe_impl == "aurora" else None
    if rounds is None and (pc.moe_impl == "aurora" or pc.ep_overlap):
        # The pipeline needs explicit rounds; traffic-blind round robin is
        # the unscheduled member of the contention-free family.
        rounds = round_robin_rounds(n_ep)

    if pc.ep_overlap:
        from repro.distributed.overlap import pipelined_local_dispatch_combine
        body = pipelined_local_dispatch_combine
    else:
        body = _local_dispatch_combine

    out_specs = (P(token_axes, None), P())
    if return_counts:
        out_specs = out_specs + (P(),)
    fn = jax.shard_map(
        lambda xs, vs, rw, ex: body(
            xs, vs, rw, ex, moe, act, ep_axes, token_axes, rounds,
            return_counts=return_counts, spec=spec),
        mesh=mesh,
        in_specs=(P(token_axes, None), P(token_axes), P(), P(ep_axes)),
        out_specs=out_specs,
        check_vma=False,
    )
    if return_counts:
        y, aux, counts = fn(xt, valid, router_w, experts)
        return y[:t], aux, counts[:t]
    y, aux = fn(xt, valid, router_w, experts)
    return y[:t], aux
