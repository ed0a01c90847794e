"""Mixture-of-Experts layer: router, capacity dispatch, expert FFN, combine.

Four dispatch implementations share the same routing/capacity semantics:

- ``dense``  — local gather/scatter (reference; smoke tests, single device).
- ``kernel`` — sort-based ragged dispatch feeding the fused Pallas grouped
               FFN (``repro.kernels.moe_gmm``): tokens are argsorted by
               expert id, per-expert group offsets come from
               ``searchsorted``, and capacity is enforced by rank within the
               group — no (T·k, E) one-hot, no cumsum over experts. The
               serving engines' decode hot path (``kernels=True``).
- ``ep``     — expert-parallel ``shard_map`` with a monolithic
               ``lax.all_to_all`` (the production baseline the paper starts
               from; see ``repro.distributed.alltoall``).
- ``aurora`` — expert-parallel ``shard_map`` where the all-to-all is replaced
               by the paper's contention-free schedule: a static sequence of
               ``lax.ppermute`` permutation rounds (Thm 4.2 / BvN), computed
               host-side by ``repro.core.schedule`` from historical traffic.

Every path names its parts with ``jax.named_scope`` — ``moe/router``,
``moe/dispatch`` (capacity ranks and the bucket scatter), ``moe/experts``
(the expert FFNs), ``moe/exchange`` (the collectives, on a mesh) and
``moe/combine`` — so a device trace attributes each op to one of them.

Routing follows the assigned architectures: softmax top-k (phi3.5-moe) and
DeepSeek-V3 sigmoid scoring with normalized top-k gates, an optional shared
expert, and leading dense layers. The Switch-style load-balance auxiliary loss
is returned for training.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.errors import FaultError
from .layers import (KernelConfig, NO_PARALLEL, ParallelContext, ffn_apply,
                     init_ffn)


# ---------------------------------------------------------------------------
# Expert replication (hot-expert copies; placement-only)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplicationSpec:
    """Physical layout of replicated experts.

    ``counts[e]`` copies of logical expert e sit contiguously in the widened
    physical expert array (physical slots ``base[e] .. base[e]+counts[e]-1``
    all hold byte-identical weights). Routing stays in the LOGICAL frame —
    the router keeps E columns and capacity/keep/drop decisions are computed
    exactly as without replication — then each kept (token, expert, rank)
    lands on replica ``rank % counts[e]`` at bucket position
    ``rank // counts[e]`` (the deterministic shard-of-token rule). Replicas
    are pure copies, so the routed function is provably unchanged: the same
    tokens reach the same weights with the same gates; only WHERE they are
    computed moves. Hashable (tuple field), so it can ride on the frozen
    ``ParallelContext`` as a jit-static.
    """

    counts: tuple[int, ...]

    def __post_init__(self):
        if not self.counts or any(int(c) < 1 for c in self.counts):
            raise ValueError(f"replica counts must be >= 1, "
                             f"got {self.counts}")

    @property
    def n_logical(self) -> int:
        return len(self.counts)

    @property
    def n_phys(self) -> int:
        return sum(self.counts)

    @property
    def base(self) -> tuple[int, ...]:
        """First physical slot of each logical expert."""
        out, acc = [], 0
        for c in self.counts:
            out.append(acc)
            acc += c
        return tuple(out)

    @property
    def phys_to_logical(self) -> tuple[int, ...]:
        return tuple(e for e, c in enumerate(self.counts) for _ in range(c))

    @property
    def is_identity(self) -> bool:
        return all(c == 1 for c in self.counts)

    @classmethod
    def from_counts(cls, counts) -> "ReplicationSpec | None":
        """None for the identity layout (no replication)."""
        spec = cls(counts=tuple(int(c) for c in counts))
        return None if spec.is_identity else spec


def _is_experts_leaf(path) -> bool:
    names = [p.key for p in path if hasattr(p, "key")]
    return "experts" in names


def _layer_of(tree, layer):
    """Layer ``layer`` (traced) of a layer-stacked pytree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        tree)


def replicate_moe_params(params, spec: ReplicationSpec, axis: int = 1):
    """Widen every MoE layer's expert leaves to ``spec.n_phys`` physical
    experts (replicas are gathered copies). Full-model stacked-segment
    leaves are (layer_count, E, ...), so the expert axis defaults to 1 —
    the same leaf addressing as ``serving.colocated.apply_pairing``; pass
    ``axis=0`` for a standalone ``init_moe`` layer dict. Router leaves are
    untouched: routing stays logical."""
    gather = jnp.asarray(spec.phys_to_logical)

    def widen(path, leaf):
        if _is_experts_leaf(path):
            return jnp.take(leaf, gather, axis=axis)
        return leaf
    return jax.tree_util.tree_map_with_path(widen, params)


def dereplicate_moe_params(params, spec: ReplicationSpec, axis: int = 1):
    """Exact inverse of ``replicate_moe_params``: keep each logical expert's
    home copy (replicas are byte-identical, so this loses nothing)."""
    gather = jnp.asarray(spec.base)

    def narrow(path, leaf):
        if _is_experts_leaf(path):
            return jnp.take(leaf, gather, axis=axis)
        return leaf
    return jax.tree_util.tree_map_with_path(narrow, params)


def replica_arrays(spec: ReplicationSpec):
    """(base (E,), counts (E,)) as int32 device arrays for dispatch remaps."""
    return (jnp.asarray(spec.base, jnp.int32),
            jnp.asarray(spec.counts, jnp.int32))


def shrink_replication(spec: ReplicationSpec | None,
                       drop_phys) -> "ReplicationSpec | None":
    """Failover shrink: the physical slots in ``drop_phys`` are gone (their
    device died or their weights are corrupt); return the layout with those
    copies removed. Lossless as long as every logical expert keeps at least
    one copy — replicas are byte-identical — otherwise ``FaultError``: the
    last copy of an expert's weights cannot be shrunk away. Returns None
    when the survivor layout is the identity (no replication left)."""
    if spec is None:
        raise FaultError(
            f"cannot drop physical expert slots {sorted(set(drop_phys))}: "
            "no replication is active, every slot is a last copy")
    drop = {int(p) for p in drop_phys}
    for p in drop:
        if not 0 <= p < spec.n_phys:
            raise FaultError(f"physical slot {p} out of "
                             f"range({spec.n_phys})")
    p2l = spec.phys_to_logical
    counts = list(spec.counts)
    for p in drop:
        counts[p2l[p]] -= 1
    for e, c in enumerate(counts):
        if c < 1:
            raise FaultError(
                f"expert {e} would lose its last copy (dropping "
                f"{sorted(drop)} from counts {spec.counts}) — failover "
                "is only lossless while one replica survives")
    return ReplicationSpec.from_counts(counts)


def repair_moe_params(params, spec: ReplicationSpec | None, bad_phys,
                      axis: int = 1):
    """Overwrite corrupt physical expert slots from a healthy replica.

    ``bad_phys`` lists physical slots whose weights are unusable (NaN
    injection, bit flips). Each is re-cloned from another copy of the same
    LOGICAL expert — byte-identical by the replication invariant, so the
    routed function is exactly restored. ``FaultError`` when some logical
    expert has no healthy copy left (including the unreplicated case,
    where every logical expert has exactly one slot)."""
    bad = {int(p) for p in bad_phys}
    n_phys = spec.n_phys if spec is not None else None
    if n_phys is None:
        if bad:
            raise FaultError(
                f"cannot repair physical slots {sorted(bad)}: no "
                "replication is active, there is no healthy copy to clone")
        return params
    for p in bad:
        if not 0 <= p < n_phys:
            raise FaultError(f"physical slot {p} out of range({n_phys})")
    base, counts = spec.base, spec.counts
    src = list(range(n_phys))
    for p in bad:
        e = spec.phys_to_logical[p]
        healthy = [q for q in range(base[e], base[e] + counts[e])
                   if q not in bad]
        if not healthy:
            raise FaultError(
                f"expert {e} has no healthy copy left among physical slots "
                f"{list(range(base[e], base[e] + counts[e]))}")
        src[p] = healthy[0]
    gather = jnp.asarray(src)

    def heal(path, leaf):
        if _is_experts_leaf(path):
            return jnp.take(leaf, gather, axis=axis)
        return leaf
    return jax.tree_util.tree_map_with_path(heal, params)


def init_moe(key, d_model: int, moe, dtype) -> dict:
    """Parameters of one MoE layer (router + stacked experts + shared)."""
    k_r, k_e, k_s = jax.random.split(key, 3)
    ek = jax.random.split(k_e, moe.n_experts)
    experts = jax.vmap(lambda k: init_ffn(k, d_model, moe.d_ff, dtype))(ek)
    p = {
        "router": jax.random.normal(k_r, (d_model, moe.n_experts),
                                    jnp.float32) * d_model ** -0.5,
        "experts": experts,  # each leaf: (E, ...)
    }
    if moe.n_shared_experts:
        p["shared"] = init_ffn(k_s, d_model,
                               moe.shared_d_ff or moe.d_ff, dtype)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def route(router_w, x, moe):
    """Token→expert assignment.

    x: (T, d). Returns (gates (T,k), idx (T,k) int32, aux_loss scalar).
    """
    logits = (x.astype(jnp.float32) @ router_w)          # (T, E)
    if moe.router == "sigmoid":                          # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(scores, moe.top_k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
        probs = jax.nn.softmax(logits, axis=-1)          # aux loss statistics
    else:                                                # softmax top-k
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, moe.top_k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    e = moe.n_experts
    f = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p_mean = probs.mean(axis=0)
    aux = e * jnp.sum(f * p_mean)
    return gates.astype(x.dtype), idx.astype(jnp.int32), aux


def capacity(n_tokens: int, top_k: int, n_experts: int, cf: float,
             multiple: int = 8) -> int:
    """Static per-expert capacity for a token group of ``n_tokens``.

    Clamped above by ``n_tokens``: top-k experts are distinct per token, so
    one source group can never send more than ``n_tokens`` rows to a single
    expert. At decode (1–2 tokens per device) this shrinks the all-to-all
    buffers 4–8× versus the lane-aligned minimum AND makes dispatch
    drop-free (§Perf iteration 4).
    """
    c = int(n_tokens * top_k * cf / n_experts) + 1
    c = max(multiple, -(-c // multiple) * multiple)
    return min(c, max(n_tokens, 1))


def dispatch_indices(idx, n_experts: int, cap: int):
    """Assignment → capacity-bucket coordinates (one-hot reference).

    idx: (T, k). Returns (slot (T,k) int32 position inside the expert bucket,
    keep (T,k) bool — False means the token overflowed and is dropped).
    Position assignment is token-order per expert (GShard semantics).
    """
    t, k = idx.shape
    flat = idx.reshape(-1)                               # (T*k,) token-major
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot            # slots before me
    slot = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    keep = slot < cap
    return slot.reshape(t, k).astype(jnp.int32), keep.reshape(t, k)


def sort_dispatch(idx, n_experts: int, cap: int):
    """Sort-based ragged dispatch — ``dispatch_indices`` without the
    O(T·k·E) one-hot + cumsum.

    Tokens are argsorted by expert id (stable sort: ties break in token
    order, exactly GShard's position assignment), per-expert group offsets
    come from a ``searchsorted`` over the sorted ids, and a token's bucket
    slot is its rank within its group (sorted position minus group offset).

    idx: (T, k) routed expert ids. Returns
      order (T*k,) int32 — flat assignment ids in expert-sorted order
      sizes (E,)   int32 — routed rows per expert (capacity drops included:
                           this is OFFERED traffic, free routing counts)
      slot  (T, k) int32 — rank within the expert group (== the one-hot
                           path's bucket position, bit for bit)
      keep  (T, k) bool  — rank < cap (False = overflowed, dropped)
    """
    t, k = idx.shape
    flat = idx.reshape(-1)                               # (T*k,) token-major
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_e = flat[order]
    offsets = jnp.searchsorted(
        sorted_e, jnp.arange(n_experts, dtype=sorted_e.dtype),
        side="left").astype(jnp.int32)                   # (E,) group starts
    sizes = jnp.diff(offsets, append=jnp.int32(t * k))   # segment sizes
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - offsets[sorted_e]
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)
    keep = slot < cap
    return order, sizes, slot.reshape(t, k), keep.reshape(t, k)


def routed_counts(idx, n_experts: int):
    """(T, k) routed expert ids → (T, E) float32 per-token choice histogram.

    Capacity drops included — this measures OFFERED dispatch traffic, the
    quantity the deployment planner consumes. One scatter-add (no (T·k, E)
    one-hot); shared by the dense and kernel dispatch paths.
    """
    t, k = idx.shape
    rows = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
    return jnp.zeros((t, n_experts), jnp.float32).at[
        rows, idx.reshape(-1)].add(1.0)


def _experts_ffn(experts, xb, act: str):
    """Apply expert e's FFN to its capacity bucket. xb: (E, C, d)."""
    return jax.vmap(lambda p, x: ffn_apply(p, x, act))(experts, xb)


# ---------------------------------------------------------------------------
# Dense (reference) dispatch — single device / smoke tests
# ---------------------------------------------------------------------------

def moe_apply_dense(p, x, moe, act: str,
                    pc: ParallelContext = NO_PARALLEL,
                    return_counts: bool = False):
    """Reference MoE layer. x: (..., d) → (y, aux).

    ``return_counts=True`` appends a (..., E) float32 per-token histogram of
    routed expert choices (capacity drops included — it measures OFFERED
    dispatch traffic, the quantity the deployment planner consumes)."""
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)                                # (T, d)
    t = xt.shape[0]
    with jax.named_scope("moe/router"):
        gates, idx, aux = route(p["router"], xt, moe)

    with jax.named_scope("moe/dispatch"):
        cap = capacity(t, moe.top_k, moe.n_experts, moe.capacity_factor)
        slot, keep = dispatch_indices(idx, moe.n_experts, cap)
        # Scatter tokens into (E, C, d) buckets. Under replication the
        # routing above ran in the LOGICAL frame (same capacity, same
        # drops); only the bucket coordinates move: rank r of expert e
        # lands on replica r % r_e at position r // r_e (collision-free,
        # never adds drops).
        tok_ids = jnp.broadcast_to(jnp.arange(t)[:, None], idx.shape)
        e_f, s_f, t_f = idx.reshape(-1), slot.reshape(-1), tok_ids.reshape(-1)
        spec = pc.moe_replication
        if spec is not None:
            base, reps = replica_arrays(spec)
            r_f = reps[e_f]
            e_f = base[e_f] + s_f % r_f
            s_f = s_f // r_f
            n_phys = spec.n_phys
        else:
            n_phys = moe.n_experts
        buf = jnp.zeros((n_phys, cap, d), xt.dtype)
        safe_s = jnp.where(keep.reshape(-1), s_f, cap - 1)
        contrib = jnp.where(keep.reshape(-1)[:, None], xt[t_f], 0.0)
        buf = buf.at[e_f, safe_s].add(contrib)  # each kept slot hit once

    with jax.named_scope("moe/experts"):
        out_buf = _experts_ffn(p["experts"], buf, act)   # (E', C, d)

    with jax.named_scope("moe/combine"):
        # Gather back and combine with gates.
        picked = out_buf[e_f, safe_s]                    # (T*k, d)
        picked = jnp.where(keep.reshape(-1)[:, None], picked, 0.0)
        y = jnp.zeros_like(xt).at[t_f].add(
            picked * gates.reshape(-1)[:, None])
    return _shared_and_counts(p, xt, y, idx, shape, aux, moe, act, pc,
                              return_counts)


def _shared_and_counts(p, xt, y, idx, shape, aux, moe, act, pc,
                       return_counts: bool):
    """Common tail of every MoE path: add the shared expert (an expert FFN
    every token takes) and, when asked, the routed-choice histogram."""
    if "shared" in p:
        with jax.named_scope("moe/experts"):
            y = y + ffn_apply(p["shared"], xt, act, pc)
    if return_counts:
        with jax.named_scope("moe/router"):
            counts = routed_counts(idx, moe.n_experts)   # (T, E)
        return (y.reshape(shape), aux,
                counts.reshape(shape[:-1] + (moe.n_experts,)))
    return y.reshape(shape), aux


# ---------------------------------------------------------------------------
# Kernel dispatch — sort-based ragged layout feeding the Pallas grouped FFN
# ---------------------------------------------------------------------------

def moe_apply_kernel(p, x, moe, act: str,
                     pc: ParallelContext = NO_PARALLEL,
                     return_counts: bool = False, layer=None):
    """Kernelized MoE layer: same routing/capacity semantics as the dense
    reference, different machinery. x: (..., d) → (y, aux[, counts]).

    ``layer``: ``p["experts"]`` leaves are a layer stack (L, E, ...) and
    this traced index picks the layer. ``moe_gmm`` reads the layer's
    blocks from the stack; the pure-jnp backends slice it where XLA ops
    consume it, so the slice fuses.

    Dispatch is the sort-based ragged layout (``sort_dispatch``); compute is
    one of three statically-chosen backends:

    - Pallas ``moe_gmm`` with ``group_sizes`` (TPU, or interpret mode for
      validation): capacity buckets scattered through ONE gather, empty
      expert blocks skipped in-kernel.
    - compact pure-jnp (CPU decode shapes, where 2·T·k <= E·C): the FFN runs
      over exactly the T·k routed rows with per-row gathered expert weights
      — no (E, C, d) buffer exists at all, so none of the garbage-row
      compute the dense path pays at decode.
    - bucketed pure-jnp (CPU prefill shapes): the same zero-padded buckets
      as the kernel, through ``ref.moe_ffn_ref(group_sizes=...)``.

    All three drop the same tokens and combine with the same gates, so
    logits match the dense path to float tolerance.
    """
    from repro.kernels import ops as kops
    from repro.kernels.moe_gmm import align_capacity

    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)                                # (T, d)
    t = xt.shape[0]
    k, e = moe.top_k, moe.n_experts
    kc = pc.kernels or KernelConfig()
    experts = p["experts"]
    with jax.named_scope("moe/router"):
        gates, idx, aux = route(p["router"], xt, moe)

    with jax.named_scope("moe/dispatch"):
        cap = capacity(t, k, e, moe.capacity_factor)
        order, sizes, slot, keep = sort_dispatch(idx, e, cap)
        keep_f = keep.reshape(-1)
        e_f = idx.reshape(-1)
        t_f = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(-1)
        # Replication: routing/capacity ran in the LOGICAL frame above;
        # remap each kept rank to (replica r % r_e, position r // r_e).
        # ``home`` keeps the compact path exact — every replica is a
        # byte-copy of its home.
        spec = pc.moe_replication
        if spec is not None:
            base, reps = replica_arrays(spec)
            s_f = slot.reshape(-1)
            pe_f = base[e_f] + s_f % reps[e_f]           # physical expert
            ps_f = s_f // reps[e_f]                      # physical position
            home_f = base[e_f]
            n_phys = spec.n_phys
        else:
            pe_f, ps_f, home_f = e_f, slot.reshape(-1), e_f
            n_phys = e

    compact = not kops.use_pallas(kc.interpret) and 2 * t * k <= e * cap
    if compact:
        # Decode-sized: gather each routed row's expert weights and run a
        # batched matvec over the compact (T·k, d) layout.
        with jax.named_scope("moe/dispatch"):
            xg = xt[t_f]                                 # (T*k, d)
        with jax.named_scope("moe/experts"):
            w = experts if layer is None else _layer_of(experts, layer)
            hg = jnp.einsum("rd,rdf->rf", xg, w["w_gate"][home_f],
                            preferred_element_type=jnp.float32)
            hu = jnp.einsum("rd,rdf->rf", xg, w["w_up"][home_f],
                            preferred_element_type=jnp.float32)
            act_fn = jax.nn.gelu if act == "geglu" else jax.nn.silu
            h = (act_fn(hg) * hu).astype(xt.dtype)
            picked = jnp.einsum("rf,rfd->rd", h, w["w_down"][home_f],
                                preferred_element_type=jnp.float32
                                ).astype(xt.dtype)       # (T*k, d)
    else:
        # Bucketed: pad capacity so the kernel grid tiles it, scatter the
        # SORTED tokens with one index build (dropped ranks scatter out of
        # range and vanish), leave unfilled rows pointing at a zero pad row.
        with jax.named_scope("moe/dispatch"):
            cap_pad = align_capacity(cap, kc.block_c)
            pe_sorted = pe_f[order]
            pr_sorted = ps_f[order]
            keep_sorted = keep_f[order]
            dest = jnp.where(keep_sorted,
                             pe_sorted * cap_pad + pr_sorted,
                             n_phys * cap_pad)
            src = jnp.full((n_phys * cap_pad,), t, jnp.int32).at[dest].set(
                order // k, mode="drop")
            x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)],
                                    axis=0)
            buf = x_pad[src].reshape(n_phys, cap_pad, d)
            group_sizes = jnp.minimum(sizes, cap)        # logical frame
            if spec is not None:
                # Physical group g (replica j of expert e, r_e copies)
                # holds the ranks ≡ j (mod r_e) below the logical group
                # size: ceil((g-j)/r).
                p2l = jnp.asarray(spec.phys_to_logical, jnp.int32)
                j = jnp.arange(n_phys, dtype=jnp.int32) - base[p2l]
                r_p = reps[p2l]
                group_sizes = jnp.maximum(
                    0, (group_sizes[p2l] - j + r_p - 1) // r_p)
        with jax.named_scope("moe/experts"):
            out_buf = kops.moe_ffn(
                buf, experts["w_gate"], experts["w_up"], experts["w_down"],
                act=act, interpret=kc.interpret,
                group_sizes=group_sizes, layer=layer,
                block_c=kc.block_c, block_f=kc.block_f)
        with jax.named_scope("moe/combine"):
            flat_out = out_buf.reshape(n_phys * cap_pad, d)
            safe = jnp.where(keep_f, pe_f * cap_pad + ps_f, 0)
            picked = flat_out[safe]                      # (T*k, d)

    with jax.named_scope("moe/combine"):
        picked = jnp.where(keep_f[:, None], picked, 0.0)
        y = jnp.zeros_like(xt).at[t_f].add(
            picked * gates.reshape(-1)[:, None])
    return _shared_and_counts(p, xt, y, idx, shape, aux, moe, act, pc,
                              return_counts)


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map): all_to_all baseline / Aurora rounds
# ---------------------------------------------------------------------------

def moe_apply_ep(p, x, moe, act: str, pc: ParallelContext,
                 return_counts: bool = False):
    """Expert-parallel MoE layer over ``pc.ep_axes``.

    Tokens must arrive sharded so that every EP device holds a token slice
    (the transformer stack constrains x to P(data, model) before calling).
    Expert weights are sharded over the flat EP axis (experts_per_device =
    E / ep_size ≥ 1). Dispatch/return all-to-alls run inside ``shard_map``;
    ``pc.aurora_rounds`` switches the collective to the scheduled ppermute
    rounds, and ``pc.ep_overlap`` pipelines expert FFN chunks with in-flight
    rounds (``repro.distributed.overlap``).

    ``return_counts=True`` appends the same (..., E) routed-choice histogram
    the local paths emit: routing happens inside the collective, so the
    per-device count slices are scattered into the global token range and
    ``psum``-replicated in-collective (``alltoall._replicated_counts``) —
    live traffic monitoring works distributed.
    """
    from repro.distributed.alltoall import ep_dispatch_combine

    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    out = ep_dispatch_combine(
        xt, p["router"], p["experts"], moe, act, pc,
        return_counts=return_counts)
    if return_counts:
        y, aux, counts = out
    else:
        y, aux = out
    if "shared" in p:
        with jax.named_scope("moe/experts"):
            y = y + ffn_apply(p["shared"], xt, act, pc)
    if return_counts:
        return (y.reshape(shape), aux,
                counts.reshape(shape[:-1] + (moe.n_experts,)))
    return y.reshape(shape), aux


def moe_apply(p, x, moe, act: str, pc: ParallelContext = NO_PARALLEL,
              return_counts: bool = False, layer=None):
    """The MoE layer, by ``pc.moe_impl``. ``layer`` (a serving scan's
    block index) says that ``p["experts"]`` leaves are still the layer
    stack (L, E, ...): the kernel path reads it in place, and the dense
    and expert-parallel paths, whose XLA dots fuse the slice, slice it
    here."""
    if pc.moe_impl == "kernel":
        return moe_apply_kernel(p, x, moe, act, pc,
                                return_counts=return_counts, layer=layer)
    if layer is not None:
        with jax.named_scope("layer_weights"):
            p = dict(p, experts=_layer_of(p["experts"], layer))
    if pc.moe_impl in ("ep", "aurora") and pc.ep_axes:
        return moe_apply_ep(p, x, moe, act, pc, return_counts=return_counts)
    return moe_apply_dense(p, x, moe, act, pc, return_counts=return_counts)
