"""Aurora dual-model colocated serving (§6 of the paper, as a runtime).

The paper's key utilization insight: colocate experts of **two different
models** so one model's compute overlaps the other model's all-to-all
(Fig 3b) — same-model colocation (Lina) stays blocked behind its own
synchronous all-to-all.

TPU realization (DESIGN.md §3): GPU SM time-slicing has no literal TPU
analogue, so the interleave is program-level — a single jitted
``colocated_step`` evaluates model A's and model B's steps in one XLA
program. A's MoE dispatch collectives (all-to-all / ppermute rounds) are
async pairs in XLA (``collective-permute-start/done``), and B's compute is
data-independent of them, so XLA's latency-hiding scheduler hoists B's FFN
between A's start/done — the Fig 3(b) schedule, compiled in.

The expert→device pairing comes from ``AuroraPlanner.plan_colocated``; it is
applied by permuting model B's expert→device map before weights are placed
(``apply_pairing``), so the aggregated per-device traffic matches the plan.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import PlanError
from repro.models import Model
from repro.serving.config import (EngineConfig, TenantSpec, coerce_config,
                                  scale_admission)
from repro.serving.telemetry import record_adoption


def _pool_config_for(config: EngineConfig, spec: TenantSpec | None):
    """Single-tenant pool view of a (possibly multi-tenant) EngineConfig:
    kernels off (the engine kernelizes each model once, up front — the pool
    re-kernelizing would double-wrap), the tenant's own ``TenantSpec``
    installed so the pool stamps its SLO deadlines, and the shared admission
    budget scaled by the tenant's ``rate_share``."""
    admission = config.resolve_admission()
    if spec is not None and spec.rate_share is not None:
        admission = scale_admission(admission, spec.rate_share)
    # The resolved policy subsumes the chunk/budget/bucket shorthand —
    # clear those fields so the replaced config stays self-consistent.
    return dataclasses.replace(
        config, kernels=False, admission=admission, prefill_chunk=None,
        step_token_budget=None, bucket_policy="pow2",
        tenants=(spec,) if spec is not None else ())


def apply_pairing(params_b, pair: list[int], cfg_b):
    """Permute model B's expert dimension so b-expert ``pair[k]`` lands on
    the device slot of a-expert k (the planner's colocation choice).

    Expert weights live as stacked leaves (count, E, ...) under "experts";
    the router's output columns (count, d, E) are permuted with the SAME
    permutation so routing follows the moved experts — placement changes
    which device an expert sits on, never the function the model computes.
    Applying ``inverse_pair(pair)`` afterwards round-trips to the original
    params exactly.
    """
    perm = jnp.asarray(np.asarray(pair), jnp.int32)

    def permute(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        if "experts" in names:
            return jnp.take(leaf, perm, axis=1)   # (count, E, …) — E axis
        if names and names[-1] == "router":
            return jnp.take(leaf, perm, axis=-1)  # (count, d, E) — columns
        return leaf

    return jax.tree_util.tree_map_with_path(permute, params_b)


def inverse_pair(pair: list[int]) -> list[int]:
    """The permutation that undoes ``apply_pairing(·, pair, ·)``."""
    inv = [0] * len(pair)
    for slot, expert in enumerate(pair):
        inv[expert] = slot
    return inv


def reseat_pairing(params, old_pair, new_pair, cfg):
    """Re-realize a slot->expert pairing IN PLACE: undo the permutation
    currently baked into ``params`` and apply the new one.

    This is the one shared placement-identity checkpoint for every adoption
    path (dual-model re-pair, N-tenant re-group, tenant churn): both maps
    must be permutations of the expert ids — anything else would silently
    duplicate or drop experts — and given that, the round-trip is exact:
    ``apply_pairing`` moves expert weights and router columns together, so
    the composed function (and every emitted token) is unchanged. Param
    shapes are preserved, so jitted steps do not recompile.
    """
    old_pair, new_pair = list(old_pair), list(new_pair)
    n = len(old_pair)
    ids = list(range(n))
    for name, pair in (("current", old_pair), ("new", new_pair)):
        if sorted(pair) != ids:
            raise PlanError(
                f"{name} pairing {pair} is not a permutation of the expert "
                f"ids 0..{n - 1} — re-seating it would duplicate/drop "
                "experts")
    if old_pair == new_pair:
        return params
    restored = apply_pairing(params, inverse_pair(old_pair), cfg)
    return apply_pairing(restored, new_pair, cfg)


def build_lockstep_step(models: list[Model], collect_stats: bool,
                        jit: bool = True):
    """One fused decode step over N tenants — the Fig 3(b) interleave for
    any tenant count: every tenant's dispatch collectives and every other
    tenant's compute live in the same XLA program, so the latency-hiding
    scheduler overlaps them.

    Returns ``step(params_list, tokens_list, caches_list, masks_list)``
    yielding ``(logits_list, caches_list)`` — plus a per-tenant routing-
    stats list when ``collect_stats`` (the live traffic signal for
    re-planning). ``masks_list`` holds one (B,) bool row mask per tenant:
    vacant slots (and the slot of an in-flight chunked prefill) freeze
    their cache rows. The caches list is donated; the compiled program is
    shared by the dual-model and N-tenant engines.
    """
    if collect_stats:
        def step(params, tokens, caches, masks):
            outs = [m.decode_step_stats(p, t, c, mask)
                    for m, p, t, c, mask
                    in zip(models, params, tokens, caches, masks)]
            return ([o[0] for o in outs], [o[1] for o in outs],
                    [o[2] for o in outs])
    else:
        def step(params, tokens, caches, masks):
            outs = [m.decode_step(p, t, c, mask)
                    for m, p, t, c, mask
                    in zip(models, params, tokens, caches, masks)]
            return [o[0] for o in outs], [o[1] for o in outs]
    return jax.jit(step, donate_argnums=(2,)) if jit else step


@dataclasses.dataclass
class ColocatedEngine:
    """Serve two models on one mesh with interleaved steps."""

    model_a: Model
    model_b: Model
    params_a: object
    params_b: object
    jit: bool = True

    def __post_init__(self):
        def step(params_a, params_b, tok_a, tok_b, cache_a, cache_b):
            # One XLA program: A's dispatch collectives overlap B's compute
            # (and vice versa) under the latency-hiding scheduler.
            logits_a, cache_a = self.model_a.decode_step(
                params_a, tok_a, cache_a)
            logits_b, cache_b = self.model_b.decode_step(
                params_b, tok_b, cache_b)
            return logits_a, logits_b, cache_a, cache_b

        def prefill(params_a, params_b, in_a, in_b, cache_a, cache_b):
            la, cache_a = self.model_a.prefill(params_a, in_a, cache_a)
            lb, cache_b = self.model_b.prefill(params_b, in_b, cache_b)
            return la, lb, cache_a, cache_b

        # Donate both models' caches (in-place update, no per-step copy).
        self._step = (jax.jit(step, donate_argnums=(4, 5))
                      if self.jit else step)
        self._prefill = (jax.jit(prefill, donate_argnums=(4, 5))
                         if self.jit else prefill)

    def serve(self, prompts_a, prompts_b, max_new_tokens: int,
              cache_cap: int):
        """Greedy-decode both batches in lockstep. Returns (out_a, out_b)."""
        ta = jnp.asarray(prompts_a, jnp.int32)
        tb = jnp.asarray(prompts_b, jnp.int32)
        ca = self.model_a.init_cache(ta.shape[0], cache_cap)
        cb = self.model_b.init_cache(tb.shape[0], cache_cap)
        la, lb, ca, cb = self._prefill(self.params_a, self.params_b,
                                       {"tokens": ta}, {"tokens": tb},
                                       ca, cb)
        va, vb = self.model_a.cfg.vocab, self.model_b.cfg.vocab
        tok_a = jnp.argmax(la[:, -1:, :va], -1).astype(jnp.int32)
        tok_b = jnp.argmax(lb[:, -1:, :vb], -1).astype(jnp.int32)
        out_a, out_b = [tok_a], [tok_b]
        for _ in range(max_new_tokens - 1):
            la, lb, ca, cb = self._step(self.params_a, self.params_b,
                                        tok_a, tok_b, ca, cb)
            tok_a = jnp.argmax(la[:, :, :va], -1).astype(jnp.int32)
            tok_b = jnp.argmax(lb[:, :, :vb], -1).astype(jnp.int32)
            out_a.append(tok_a)
            out_b.append(tok_b)
        return (jnp.concatenate(out_a, 1), jnp.concatenate(out_b, 1))


class ColocatedContinuousEngine:
    """Continuous batching for the Aurora dual-model runtime.

    Two ``ContinuousEngine`` slot pools — one per model — admit from their
    own request queues and decode in **lockstep** through one fused jitted
    step, preserving the Fig 3(b) overlap: model A's dispatch collectives
    and model B's compute live in the same XLA program, so the latency-
    hiding scheduler interleaves them exactly as in ``ColocatedEngine``,
    while each pool's slots fill and drain independently with traffic.

    With ``replan=OnlineReplanner(...)`` the engine closes the paper's
    §2.4 loop online: both pools harvest live per-layer routing counts into
    ``TrafficMonitor``s, and every ``replan.interval`` lockstep decodes the
    planner re-pairs from the live traces. An adopted plan is applied IN
    PLACE by un-permuting model B's experts with ``inverse_pair`` and
    re-permuting with the new pairing — placement-only, so a mid-stream
    re-plan never changes any emitted token.
    """

    def __init__(self, model_a: Model, model_b: Model, params_a, params_b,
                 batch_slots: int, cache_cap: int,
                 config: EngineConfig | None = None,
                 pair: list[int] | None = None,
                 replan=None, monitor_halflife: float = 128.0, **legacy):
        from .engine import ContinuousEngine
        from .monitor import TrafficMonitor

        config = coerce_config(config, legacy, type(self).__name__)
        self.config = config
        # Kernelize BEFORE the pools and the fused lockstep step are built,
        # so both models' decode/prefill programs share the path.
        model_a = config.kernelize(model_a)
        model_b = config.kernelize(model_b)
        self.model_a, self.model_b = model_a, model_b
        self.replan = replan
        self.monitor_a = self.monitor_b = None
        if replan is not None:
            ca, cb = model_a.cfg, model_b.cfg
            if (ca.moe is None or cb.moe is None
                    or ca.moe.n_experts != cb.moe.n_experts):
                raise ValueError(
                    "online re-planning needs two MoE models with equal "
                    "expert counts (the pairing is expert<->expert)")
            if model_a.n_moe_layers != model_b.n_moe_layers:
                raise ValueError(
                    "online re-planning needs equal MoE layer counts "
                    "(the planner simulates the traces layer-by-layer)")
            self.monitor_a = TrafficMonitor(
                ca.moe.n_experts, model_a.n_moe_layers, name=ca.arch_id,
                halflife=monitor_halflife)
            self.monitor_b = TrafficMonitor(
                cb.moe.n_experts, model_b.n_moe_layers, name=cb.arch_id,
                halflife=monitor_halflife)
        # The pairing currently REALIZED in pool_b's params (identity unless
        # the caller already applied a plan) — what a re-plan must undo.
        n_e = model_b.cfg.moe.n_experts if model_b.cfg.moe else 0
        self.pair = list(pair) if pair is not None else list(range(n_e))
        self.plan = None                        # last adopted online plan
        if self.monitor_b is not None:
            # Pool B's routing stats arrive in SLOT space (apply_pairing
            # permuted the router columns); the monitor translates them
            # back to original expert ids so the planner's traces and the
            # candidate pairings stay in one frame.
            self.monitor_b.slot_to_expert = list(self.pair)

        # Each pool gets a single-tenant view of the config: kernels off
        # (the models above are already kernelized), its own TenantSpec for
        # SLO deadlines, and its rate-share slice of the admission budget.
        if config.tenants and len(config.tenants) != 2:
            raise ValueError(
                f"{len(config.tenants)} TenantSpecs for the dual-model "
                "engine — declare exactly two (model A then model B) or "
                "none")
        self.tenant_specs = (list(config.tenants) if config.tenants
                             else [None, None])
        self.pool_a = ContinuousEngine(
            model_a, params_a, batch_slots, cache_cap,
            config=_pool_config_for(config, self.tenant_specs[0]),
            monitor=self.monitor_a)
        self.pool_b = ContinuousEngine(
            model_b, params_b, batch_slots, cache_cap,
            config=_pool_config_for(config, self.tenant_specs[1]),
            monitor=self.monitor_b)

        self._jit = config.jit
        self._step_wrapper = config.step_wrapper or (lambda fn: fn)
        self._telemetry = config.telemetry
        if replan is not None and config.telemetry is not None \
                and getattr(replan, "telemetry", None) is None:
            replan.telemetry = config.telemetry
        self._build_lockstep()
        self.decode_steps = 0

    def _build_lockstep(self) -> None:
        """(Re)build the fused lockstep step from the pools' current models
        (rebuilt when a distributed engine swaps ppermute rounds)."""
        step = self._step_wrapper(build_lockstep_step(
            [self.model_a, self.model_b],
            collect_stats=self.replan is not None, jit=self._jit))
        if self._telemetry is not None:
            step = self._telemetry.wrap_step(step, "lockstep_decode")
        self._step = step

    @property
    def replan_events(self) -> list:
        return [] if self.replan is None else self.replan.events

    def adopt(self, plan) -> None:
        """Adopt a colocation ``Plan`` mid-stream: re-realize its pairing on
        pool B's params via the shared ``reseat_pairing`` checkpoint.
        Placement-only — param shapes are unchanged, so the jitted step does
        not recompile and in-flight token streams are unaffected."""
        new_pair = list(plan.pair)
        self.pool_b.params = reseat_pairing(self.pool_b.params, self.pair,
                                            new_pair, self.model_b.cfg)
        self.pair = new_pair
        if self.monitor_b is not None:
            self.monitor_b.slot_to_expert = list(new_pair)
        self.plan = plan
        record_adoption(self._telemetry, "pairing", step=self.decode_steps,
                        pair=new_pair)

    def _adopt_online(self, plan) -> None:
        """Seam for the replanner loop (the distributed engine layers an
        Aurora-rounds refresh on top)."""
        self.adopt(plan)

    def _maybe_replan(self) -> None:
        new = self.replan.maybe_replan(self.decode_steps, self.monitor_a,
                                       self.monitor_b, self.pair)
        if new is not None:
            self._adopt_online(new)

    def step(self) -> bool:
        """Admit into both pools, then one fused lockstep decode."""
        tel = self._telemetry
        if tel is None or not tel.enabled:
            return self._step_impl()
        with tel.span("lockstep_step", step=self.decode_steps):
            return self._step_impl()

    def _step_impl(self) -> bool:
        a, b = self.pool_a, self.pool_b
        worked_a = a._admit_tick()
        worked_b = b._admit_tick()
        if a.num_active == 0 and b.num_active == 0:
            return worked_a or worked_b
        mask_a = np.array([r is not None for r in a.slots], bool)
        mask_b = np.array([r is not None for r in b.slots], bool)
        masks = [jnp.asarray(mask_a), jnp.asarray(mask_b)]
        if self.replan is not None:
            (la, lb), (a.cache, b.cache), (sa, sb) = self._step(
                [a.params, b.params], [a.tokens, b.tokens],
                [a.cache, b.cache], masks)
            self.monitor_a.observe(sa, mask_a)
            self.monitor_b.observe(sb, mask_b)
        else:
            (la, lb), (a.cache, b.cache) = self._step(
                [a.params, b.params], [a.tokens, b.tokens],
                [a.cache, b.cache], masks)
        self.decode_steps += 1
        a._postdecode(la)
        b._postdecode(lb)
        if self.replan is not None:
            self._maybe_replan()
        return True

    def serve(self, reqs_a, reqs_b):
        """Run both request streams to completion (``Request.arrival`` in
        lockstep-step units). Returns (reqs_a, reqs_b)."""
        from .engine import serve_stream

        serve_stream(self.step, [(self.pool_a, reqs_a),
                                 (self.pool_b, reqs_b)])
        return reqs_a, reqs_b


class MultiTenantContinuousEngine:
    """Continuous batching over N >= 2 colocated tenants.

    The dual-model engine generalized: one ``ContinuousEngine`` slot pool per
    tenant, each admitting from its own queue under the shared chunked-
    prefill budget scheduler, all decoding in lockstep through ONE fused
    jitted step (``build_lockstep_step``) — N tenants' collectives and
    compute in a single XLA program, so any tenant's dispatch overlaps the
    others' FFNs (the paper's §6 insight, N-fold).

    ``groups[g] = (e_0, .., e_{N-1})`` is the planner's k-way colocation
    choice (``AuroraPlanner.plan_multi``): tenant t's expert ``groups[g][t]``
    occupies device slot g, tenant 0 anchoring the slots
    (``groups[g][0] == g``). The grouping is REALIZED by the caller permuting
    tenant t's params with ``apply_pairing(params_t, [g[t] for g in groups])``
    for t >= 1 — placement-only, so any grouping serves identical tokens.

    Alternatively, construct from ``config.tenants`` alone: each
    ``TenantSpec`` carries its model, LOGICAL params, placement ``pair``,
    and SLO targets; the engine realizes the pairings, derives ``groups``,
    and gives every tenant's pool its own deadline source and rate-share
    slice of the admission budget — the same spec type ``admit_tenant``
    accepts for live churn.

    With ``replan=OnlineReplanner(...)`` every tenant harvests live routing
    counts into its own ``TrafficMonitor`` and the planner periodically
    re-groups from the N live traces (``OnlineReplanner.maybe_regroup``);
    an adopted grouping is applied in place per tenant via
    ``inverse_pair`` + ``apply_pairing`` — again placement-only, token
    streams provably unchanged.
    """

    def __init__(self, models: list[Model] | None = None,
                 params: list | None = None, batch_slots: int = None,
                 cache_cap: int = None, config: EngineConfig | None = None,
                 groups: list[tuple[int, ...]] | None = None,
                 replan=None, monitor_halflife: float = 128.0, **legacy):
        from .engine import ContinuousEngine
        from .monitor import TrafficMonitor

        if batch_slots is None or cache_cap is None:
            raise TypeError("batch_slots and cache_cap are required")
        config = coerce_config(config, legacy, type(self).__name__)
        self.config = config
        if models is None:
            # Config-driven construction: every tenant (model, params,
            # placement) comes from one validated TenantSpec — the same
            # spec type admit_tenant accepts for live churn.
            if params is not None:
                raise ValueError("params without models — declare both on "
                                 "the TenantSpecs instead")
            if groups is not None:
                raise ValueError("groups conflict with config-driven "
                                 "construction — declare per-tenant "
                                 "placement via TenantSpec.pair")
            specs = list(config.tenants)
            if len(specs) < 2:
                raise ValueError(
                    "config-driven construction needs >= 2 TenantSpecs in "
                    "config.tenants (or pass models/params explicitly)")
            missing = [t for t, s in enumerate(specs)
                       if s.model is None or s.params is None]
            if missing:
                raise ValueError(
                    f"TenantSpecs {missing} declare no model/params — "
                    "config-driven construction needs both on every spec")
            models = [s.model for s in specs]
            n_e = (models[0].cfg.moe.n_experts
                   if models[0].cfg.moe is not None else 0)
            pairs = [list(s.pair) if s.pair is not None else list(range(n_e))
                     for s in specs]
            if pairs and pairs[0] != list(range(len(pairs[0]))):
                raise ValueError("tenant 0 anchors the slots — its "
                                 "TenantSpec.pair must be the identity")
            # Specs carry LOGICAL (unpermuted) params; realize each
            # tenant's placement here, exactly as admit_tenant does.
            params = [apply_pairing(s.params, p, s.model.cfg)
                      if p != list(range(len(p))) else s.params
                      for s, p in zip(specs, pairs)]
            groups = [tuple(p[g] for p in pairs)
                      for g in range(len(pairs[0]) if pairs else 0)] or None
        else:
            specs = list(config.tenants)
            if specs and len(specs) != len(models):
                raise ValueError(f"{len(specs)} TenantSpecs for "
                                 f"{len(models)} models — declare one per "
                                 "tenant or none")
        self.tenant_specs = specs or [None] * len(models)
        if len(models) < 2:
            raise ValueError("MultiTenantContinuousEngine needs >= 2 tenants "
                             "(use ContinuousEngine for one)")
        if len(params) != len(models):
            raise ValueError("one params tree per model required")
        models = [config.kernelize(m) for m in models]
        self.models = list(models)
        self.n_tenants = len(models)
        self.batch_slots = batch_slots
        self.cache_cap = cache_cap
        self.monitor_halflife = monitor_halflife
        self.replan = replan
        self.monitors = None
        if replan is not None:
            cfgs = [m.cfg for m in models]
            if (any(c.moe is None for c in cfgs)
                    or len({c.moe.n_experts for c in cfgs}) != 1):
                raise ValueError(
                    "online re-grouping needs MoE tenants with equal expert "
                    "counts (the grouping is expert<->expert)")
            if len({m.n_moe_layers for m in models}) != 1:
                raise ValueError(
                    "online re-grouping needs equal MoE layer counts "
                    "(the planner simulates the traces layer-by-layer)")
            self.monitors = [
                TrafficMonitor(c.moe.n_experts, m.n_moe_layers,
                               name=f"{c.arch_id}#{t}",
                               halflife=monitor_halflife)
                for t, (m, c) in enumerate(zip(models, cfgs))]
        # The grouping currently REALIZED in the tenants' params (identity
        # unless the caller already applied a plan) — what a re-group must
        # undo, per tenant.
        n_e = models[0].cfg.moe.n_experts if models[0].cfg.moe else 0
        if groups is None:
            groups = [(g,) * self.n_tenants for g in range(n_e)]
        self.groups = [tuple(g) for g in groups]
        if n_e and len(self.groups) != n_e:
            raise ValueError(f"{len(self.groups)} groups for {n_e} experts "
                             "(one device slot per expert group)")
        for g, grp in enumerate(self.groups):
            if len(grp) != self.n_tenants:
                raise ValueError(f"group {g} has {len(grp)} entries for "
                                 f"{self.n_tenants} tenants")
            if grp[0] != g:
                raise ValueError("tenant 0 anchors the slots: "
                                 f"groups[{g}][0] must be {g}, got {grp[0]}")
        for t in range(1, self.n_tenants):
            if sorted(g[t] for g in self.groups) != list(
                    range(len(self.groups))):
                raise ValueError(f"tenant {t}'s column is not a permutation "
                                 "of the expert ids (each expert must sit "
                                 "on exactly one slot)")
        self.plan = None                        # last adopted online plan
        if self.monitors is not None:
            # Permuted tenants' routing stats arrive in SLOT space; each
            # monitor translates back to original expert ids (tenant 0 is
            # the identity anchor and needs no translation).
            for t in range(1, self.n_tenants):
                self.monitors[t].slot_to_expert = [g[t] for g in self.groups]

        # Each pool gets a single-tenant view of the config (kernels off,
        # its own TenantSpec, rate-share-scaled admission budget).
        self.pools = [
            ContinuousEngine(m, p, batch_slots, cache_cap,
                             config=_pool_config_for(
                                 config, self.tenant_specs[t]),
                             monitor=(self.monitors[t] if self.monitors
                                      else None))
            for t, (m, p) in enumerate(zip(models, params))]
        self._jit = config.jit
        self._step_wrapper = config.step_wrapper or (lambda fn: fn)
        self._telemetry = config.telemetry
        if replan is not None and config.telemetry is not None \
                and getattr(replan, "telemetry", None) is None:
            replan.telemetry = config.telemetry
        self._build_lockstep()
        self.decode_steps = 0

    def _build_lockstep(self) -> None:
        """(Re)build the fused N-tenant step from the pools' current models
        (rebuilt when a distributed engine swaps ppermute rounds)."""
        step = self._step_wrapper(build_lockstep_step(
            self.models, collect_stats=self.replan is not None,
            jit=self._jit))
        if self._telemetry is not None:
            step = self._telemetry.wrap_step(step, "lockstep_decode")
        self._step = step

    @property
    def replan_events(self) -> list:
        return [] if self.replan is None else self.replan.events

    def tenant_pair(self, t: int) -> list[int]:
        """Slot->expert permutation realized for tenant t."""
        return [g[t] for g in self.groups]

    def adopt(self, plan) -> None:
        """Adopt a k-way grouping ``Plan`` mid-stream: per tenant, re-seat
        the realized slot->expert permutation to the plan's via the shared
        ``reseat_pairing`` checkpoint. Placement-only — param shapes are
        unchanged, so the fused step does not recompile and in-flight token
        streams are unaffected. All tenants are re-seated (tenant 0 included
        — after churn the anchor column need not be the identity)."""
        new_groups = [tuple(g) for g in plan.groups]
        if any(len(g) != self.n_tenants for g in new_groups):
            raise PlanError(
                f"plan groups tenant count {[len(g) for g in new_groups]} "
                f"!= engine tenant count {self.n_tenants}")
        for t in range(self.n_tenants):
            old_p = self.tenant_pair(t)
            new_p = [g[t] for g in new_groups]
            if old_p == new_p:
                continue
            self.pools[t].params = reseat_pairing(
                self.pools[t].params, old_p, new_p, self.models[t].cfg)
            if self.monitors is not None:
                self.monitors[t].slot_to_expert = new_p
        self.groups = new_groups
        self.plan = plan
        record_adoption(self._telemetry, "grouping", step=self.decode_steps,
                        groups=new_groups)

    def _adopt_online(self, plan) -> None:
        """Seam for the replanner loop (the distributed engine layers an
        Aurora-rounds refresh on top)."""
        self.adopt(plan)

    def _maybe_regroup(self) -> None:
        new = self.replan.maybe_regroup(self.decode_steps, self.monitors,
                                        self.groups)
        if new is not None:
            self._adopt_online(new)

    # -- tenant churn ------------------------------------------------------
    def admit_tenant(self, model: Model | TenantSpec = None, params=None, *,
                     pair: list[int] | None = None,
                     spec: TenantSpec | None = None) -> int:
        """Admit a NEW tenant into the live pool. Returns its tenant index.

        Accepts either a ``TenantSpec`` carrying model/params/pair (and SLO
        targets, honored by the new pool) — the same validated type
        ``EngineConfig.tenants`` uses for construction — or the unbundled
        ``(model, params, pair=...)`` spelling. ``params`` arrive in the
        LOGICAL (unpermuted) frame; ``pair`` is the slot->expert placement
        to realize for it (identity when omitted) — realized here via
        ``apply_pairing``, exactly as the constructor documents for
        pre-permuted tenants. The tenant gets its own slot pool and (under
        a replanner) its own ``TrafficMonitor``; colocation groups gain its
        column, and the replanner re-derives the grouping online once the
        fresh monitor passes warmup. Every existing tenant's pool, cache,
        and token stream are untouched — admission is placement-only for
        the incumbents (lockstep rows are tenant-independent).
        """
        from .engine import ContinuousEngine
        from .monitor import TrafficMonitor

        if isinstance(model, TenantSpec):
            if spec is not None:
                raise ValueError("pass the TenantSpec once (positionally "
                                 "or as spec=, not both)")
            spec, model = model, None
        if spec is not None:
            if model is not None or params is not None or pair is not None:
                raise ValueError("pass EITHER a TenantSpec or unbundled "
                                 "model/params/pair — not both")
            if spec.model is None or spec.params is None:
                raise ValueError("admit_tenant needs model and params on "
                                 "the TenantSpec")
            model, params, pair = spec.model, spec.params, spec.pair
        elif model is None or params is None:
            raise TypeError("admit_tenant needs a TenantSpec or "
                            "(model, params)")
        model = self.config.kernelize(model)
        cfg = model.cfg
        n_e = len(self.groups)
        if self.replan is not None:
            if cfg.moe is None or cfg.moe.n_experts != n_e:
                raise ValueError(
                    "online re-grouping needs MoE tenants with equal expert "
                    "counts (the grouping is expert<->expert)")
            if model.n_moe_layers != self.models[0].n_moe_layers:
                raise ValueError(
                    "online re-grouping needs equal MoE layer counts "
                    "(the planner simulates the traces layer-by-layer)")
        pair = list(pair) if pair is not None else list(range(n_e))
        if n_e and sorted(pair) != list(range(n_e)):
            raise ValueError(f"pair {pair} is not a permutation of the "
                             f"expert ids 0..{n_e - 1}")
        if pair != list(range(n_e)):
            params = apply_pairing(params, pair, cfg)
        t = self.n_tenants
        monitor = None
        if self.monitors is not None:
            monitor = TrafficMonitor(n_e, model.n_moe_layers,
                                     name=f"{cfg.arch_id}#{t}",
                                     halflife=self.monitor_halflife)
            monitor.slot_to_expert = list(pair)
            self.monitors.append(monitor)
        self.models.append(model)
        self.pools.append(ContinuousEngine(
            model, params, self.batch_slots, self.cache_cap,
            config=_pool_config_for(self.config, spec), monitor=monitor))
        self.tenant_specs.append(spec)
        self.groups = [grp + (pair[g],) for g, grp in enumerate(self.groups)]
        self.n_tenants += 1
        self._build_lockstep()
        return t

    def evict_tenant(self, t: int):
        """Remove tenant ``t`` from the live pool. Returns its (detached)
        slot pool — still serveable standalone.

        The tenant's queued and in-flight requests leave with its pool
        (drain the engine first to finish them); its colocation column,
        monitor, and lockstep row disappear. Every surviving tenant's pool
        and cache are untouched, so eviction is placement-only for them —
        their token streams are byte-identical to a churn-free run.
        """
        if not 0 <= t < self.n_tenants:
            raise ValueError(f"no tenant {t} (have {self.n_tenants})")
        if self.n_tenants <= 1:
            raise ValueError("cannot evict the last tenant")
        if self.n_tenants == 2 and self.replan is not None:
            raise ValueError(
                "eviction would leave one tenant — nothing to re-group; "
                "drop the replanner (or keep >= 2 tenants)")
        pool = self.pools.pop(t)
        self.models.pop(t)
        self.tenant_specs.pop(t)
        if self.monitors is not None:
            self.monitors.pop(t)
        self.groups = [g[:t] + g[t + 1:] for g in self.groups]
        self.n_tenants -= 1
        self._build_lockstep()
        return pool

    def step(self) -> bool:
        """Admit into every pool, then one fused lockstep decode."""
        tel = self._telemetry
        if tel is None or not tel.enabled:
            return self._step_impl()
        with tel.span("lockstep_step", step=self.decode_steps,
                      tenants=self.n_tenants):
            return self._step_impl()

    def _step_impl(self) -> bool:
        worked = [p._admit_tick() for p in self.pools]
        if all(p.num_active == 0 for p in self.pools):
            return any(worked)
        masks = [np.array([r is not None for r in p.slots], bool)
                 for p in self.pools]
        jmasks = [jnp.asarray(m) for m in masks]
        if self.replan is not None:
            logits, caches, stats = self._step(
                [p.params for p in self.pools],
                [p.tokens for p in self.pools],
                [p.cache for p in self.pools], jmasks)
            for mon, s, mask in zip(self.monitors, stats, masks):
                mon.observe(s, mask)
        else:
            logits, caches = self._step(
                [p.params for p in self.pools],
                [p.tokens for p in self.pools],
                [p.cache for p in self.pools], jmasks)
        for p, c in zip(self.pools, caches):
            p.cache = c
        self.decode_steps += 1
        for p, lg in zip(self.pools, logits):
            p._postdecode(lg)
        if self.replan is not None:
            self._maybe_regroup()
        return True

    def serve(self, streams: list[list]) -> list[list]:
        """Run one request stream per tenant to completion
        (``Request.arrival`` in lockstep-step units)."""
        from .engine import serve_stream

        if len(streams) != self.n_tenants:
            raise ValueError(f"{self.n_tenants} tenants need "
                             f"{self.n_tenants} request streams")
        serve_stream(self.step, list(zip(self.pools, streams)))
        return streams
