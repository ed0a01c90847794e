"""EP-sharded distributed serving: the continuous engines on a real mesh.

This is the layer that turns the planner/simulator/kernel stack into an
actual distributed server. The three continuous engines run unchanged
host-side schedulers; only their compiled step programs change:

- the MoE hot path dispatches expert-parallel over the mesh's flat EP axis
  (``moe_impl="ep"``: monolithic all_to_all; ``"aurora"``: the paper's BvN
  ppermute rounds; ``overlap=True``: rounds software-pipelined with the
  grouped expert FFN — ``repro.distributed.overlap``);
- live routing counts keep flowing to ``TrafficMonitor`` (the EP paths now
  psum them in-collective), so online re-planning works distributed;
- a replan **also refreshes the BvN rounds**: ``adopt(plan)`` recomputes
  ``aurora_schedule`` → ``aurora_rounds_from_schedule`` at device granularity
  and swaps the rounds into freshly compiled steps. The swap is
  placement-only — rounds change *when* bytes move, never what arrives —
  so in-flight token streams are unaffected (tested).

CI has no multi-chip hardware; the mesh is a host-platform device mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8

set **before** the jax backend initializes (``repro.launch.mesh
.force_host_device_count``). Everything here is shape- and
collective-identical to a TPU/GPU mesh run.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.errors import PlanError
from repro.core.schedule import aurora_schedule
from repro.core.traffic import MoETrace, strip_diagonal
from repro.distributed.alltoall import (aurora_rounds_from_schedule,
                                        round_robin_rounds,
                                        validate_rounds_cover)
from repro.models import Model
from repro.sharding import make_pc

from .colocated import ColocatedContinuousEngine, MultiTenantContinuousEngine
from .config import EngineConfig, coerce_config
from .engine import ContinuousEngine
from .telemetry import record_adoption


# ---------------------------------------------------------------------------
# Rounds derivation: expert-granularity plans → device-granularity ppermutes
# ---------------------------------------------------------------------------

def device_traffic(d: np.ndarray, n_devices: int) -> np.ndarray:
    """Aggregate an (E, E) expert-granularity traffic matrix onto the EP
    devices hosting the experts.

    Experts shard over the flat EP axis in contiguous blocks (expert e lives
    on device ``e // (E / n_devices)`` — the layout ``P(ep_axes)`` realizes
    on the stacked (E, ...) weight leaves), so device-pair traffic is the
    block sum. The diagonal (now including intra-device expert pairs) is
    stripped: self-traffic never crosses the network.
    """
    d = np.asarray(d, dtype=np.float64)
    e = d.shape[0]
    if d.ndim != 2 or d.shape[1] != e:
        raise ValueError(f"traffic matrix must be square, got {d.shape}")
    if n_devices <= 0 or e % n_devices:
        raise ValueError(f"{e} experts do not shard over {n_devices} devices")
    epd = e // n_devices
    agg = d.reshape(n_devices, epd, n_devices, epd).sum(axis=(1, 3))
    return strip_diagonal(agg)


def rounds_from_traffic(d: np.ndarray, n_ep: int):
    """BvN ppermute rounds for an expert- or device-granularity matrix."""
    d = np.asarray(d, dtype=np.float64)
    if d.shape[0] != n_ep:
        d = device_traffic(d, n_ep)
    sched = aurora_schedule(strip_diagonal(d))
    return aurora_rounds_from_schedule(sched, n_ep)


def rounds_from_plan(plan, n_ep: int):
    """Device-granularity rounds from a planner ``Plan``.

    The plan's per-layer ``CommSchedule``s live at expert granularity (the
    cluster the planner models has one slot per expert); their realized
    traffic matrices (``CommSchedule.traffic``) are averaged over layers —
    one static round sequence serves every MoE layer of the compiled step —
    and re-scheduled at device granularity.
    """
    mats = [s.traffic() for s in plan.schedules if s.slots]
    if not mats:
        return round_robin_rounds(n_ep)
    return rounds_from_traffic(np.mean(mats, axis=0), n_ep)


def rounds_from_trace(trace: MoETrace, n_ep: int):
    """Device-granularity rounds from a (historical or live) ``MoETrace``."""
    return rounds_from_traffic(np.mean(trace.layers, axis=0), n_ep)


def resolve_rounds(source, n_ep: int):
    """Rounds from whatever traffic evidence the caller has: a ``Plan``
    (uses its schedules), a ``MoETrace``, or a raw traffic matrix.

    Explicit round sequences are deliberately NOT accepted — an (R, n)
    stack of dst vectors is indistinguishable from a traffic matrix when
    R == n (8 devices routinely schedule into exactly 8 rounds). Callers
    holding literal rounds use ``swap_rounds`` / the ``rounds=`` ctor
    argument, which install them after a full-cover validation.
    """
    if hasattr(source, "schedules"):
        return rounds_from_plan(source, n_ep)
    if isinstance(source, MoETrace):
        return rounds_from_trace(source, n_ep)
    arr = np.asarray(source)
    if arr.ndim == 2 and arr.dtype != object and arr.shape[0] == arr.shape[1]:
        return rounds_from_traffic(arr, n_ep)
    raise TypeError(
        "adopt()/resolve_rounds take traffic evidence — a Plan, a MoETrace, "
        f"or a square traffic matrix — got {type(source).__name__}; to "
        "install literal ppermute rounds, call swap_rounds (or pass "
        "rounds=... at construction)")


# ---------------------------------------------------------------------------
# Model distribution
# ---------------------------------------------------------------------------

def ep_size(pc) -> int:
    n = 1
    for ax in pc.ep_axes or ():
        n *= pc.mesh.shape[ax]
    return n


def distribute(model: Model, mesh, moe_impl: str = "aurora",
               overlap: bool = False) -> Model:
    """Bind an EP-sharded ``ParallelContext`` for ``mesh`` onto ``model``.

    Unlike ``make_pc``'s silent dense fallback, this *demands* expert
    parallelism: a config whose expert count does not divide the mesh's EP
    axis is an error here (the caller asked for a distributed MoE server).
    """
    if model.cfg.moe is None:
        raise ValueError(f"{model.cfg.arch_id} has no MoE layers — "
                         "distributed EP serving needs experts to shard")
    pc = make_pc(model.cfg, mesh, moe_impl=moe_impl)
    if pc.moe_impl not in ("ep", "aurora"):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        raise ValueError(
            f"{model.cfg.moe.n_experts} experts do not shard over this mesh "
            f"({sizes}): the expert count must divide the flat EP axis "
            "(data*model, or model alone)")
    pc = dataclasses.replace(pc, ep_overlap=overlap,
                             kernels=model.pc.kernels)
    return dataclasses.replace(model, pc=pc)


def _ctor_rounds(rounds, plan, n_ep: int):
    """Shared constructor logic of the three Distributed* engines: literal
    rounds win (validated as a full cover), else derive them from the
    plan's traffic evidence; None means round-robin until adoption."""
    if rounds is None and plan is not None:
        return resolve_rounds(plan, n_ep)
    if rounds is not None:
        return validate_rounds_cover(rounds, n_ep)
    return None


def _with_rounds(model: Model, rounds) -> Model:
    return dataclasses.replace(
        model, pc=dataclasses.replace(model.pc, aurora_rounds=rounds))


def _require_aurora(pc) -> None:
    """Rounds only steer the 'aurora' dispatch path; swapping them on 'ep'
    would pay a full recompile for a schedule the monolithic all_to_all
    never reads."""
    if pc.moe_impl != "aurora":
        raise ValueError("rounds only exist on the 'aurora' dispatch path, "
                         f"this engine runs '{pc.moe_impl}'")


def _with_mesh(mesh):
    """Step wrapper: run a compiled step under the mesh context (bare
    ``PartitionSpec`` sharding constraints resolve against it)."""
    def wrap(fn):
        def run(*args, **kwargs):
            with jax.set_mesh(mesh):
                return fn(*args, **kwargs)
        return run
    return wrap


def _compose_wrapper(user, mesh):
    """Mesh-context wrapper composed UNDER any user ``step_wrapper`` (the
    mesh must be innermost — it has to be active when the compiled step
    actually runs)."""
    inner = _with_mesh(mesh)
    return inner if user is None else (lambda fn: user(inner(fn)))


def _mesh_config(config, kw, owner, mesh):
    """Resolve the effective ``EngineConfig`` for a Distributed* engine and
    compose the mesh-context wrapper under any user ``step_wrapper``.
    Legacy keywords are coerced here non-strictly: ``kw`` still carries
    real pass-through arguments (``monitor``, ``pair``, ...) for the parent
    constructor, which runs the strict pass on the rest. Returns
    ``(config, user_wrapper)`` — the engines stash the USER's original
    wrapper so a degraded mesh rebuild (``adopt_degraded``) can recompose
    it around the survivor mesh's context."""
    config = coerce_config(config, kw, owner, strict=False)
    user = config.step_wrapper
    wrapper = _compose_wrapper(user, mesh)
    return dataclasses.replace(config, step_wrapper=wrapper), user


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------

class DistributedEngine(ContinuousEngine):
    """``ContinuousEngine`` with its jitted steps EP-sharded over a mesh.

    ``moe_impl="aurora"`` (default) runs the scheduled ppermute rounds —
    traffic-blind round robin until a plan is adopted; ``overlap=True``
    pipelines the grouped expert FFN with in-flight rounds. ``adopt(plan)``
    refreshes the rounds from a fresh plan/trace/traffic matrix mid-stream
    (placement-only: recompiles the steps, never changes a token).
    """

    def __init__(self, model: Model, params, batch_slots: int,
                 cache_cap: int, *, mesh, moe_impl: str = "aurora",
                 rounds=None, plan=None, overlap: bool = False,
                 config: EngineConfig | None = None, **kw):
        config, self._user_wrapper = _mesh_config(
            config, kw, type(self).__name__, mesh)
        model = distribute(model, mesh, moe_impl=moe_impl, overlap=overlap)
        self.mesh = mesh
        self.n_ep = ep_size(model.pc)
        rounds = _ctor_rounds(rounds, plan, self.n_ep)
        if rounds is not None:
            model = _with_rounds(model, rounds)
        super().__init__(model, params, batch_slots, cache_cap,
                         config=config, **kw)

    @property
    def rounds(self):
        return self.model.pc.aurora_rounds

    def swap_rounds(self, rounds) -> None:
        """Swap the compiled ppermute schedule — placement-only: serving
        state (cache, slots, queue) is untouched and token streams are
        provably unchanged (the rounds decide WHEN buckets move, never what
        arrives)."""
        _require_aurora(self.model.pc)
        pc = dataclasses.replace(
            self.model.pc,
            aurora_rounds=validate_rounds_cover(rounds, self.n_ep))
        self._rebind(dataclasses.replace(self.model, pc=pc))
        record_adoption(self._telemetry, "rounds", step=self.decode_steps,
                        n_rounds=len(pc.aurora_rounds))

    def adopt(self, plan):
        """Refresh the BvN rounds from a fresh ``Plan`` / ``MoETrace`` /
        traffic matrix (closing the PR 2 follow-up: a replan now refreshes
        the communication schedule, not just the placement). A full ``Plan``
        also carries its hot-expert replication: the expert leaves are
        re-widened under the new host map (placement-only — see
        ``ContinuousEngine._set_replication``) before the rounds swap, so
        one adoption moves placement AND schedule together. An exclusive
        plan whose only content is a fresh expert→device assignment
        (scenario 2: ``OnlineReplanner.maybe_reassign``) re-seats the
        expert leaves onto their new EP blocks first — placement-only as
        well. Returns the adopted rounds."""
        if hasattr(plan, "schedules"):   # a full Plan carries placement too
            if (plan.pair is None and plan.groups is None
                    and plan.replication is None
                    and self.assignment is not None
                    and len(plan.expert_to_device) == len(self.assignment)):
                self.adopt_assignment(plan.expert_to_device)
            rep = plan.replication
            if rep is not None:
                n_phys = sum(len(h) for h in rep)
                if n_phys % self.n_ep:
                    raise PlanError(
                        f"plan replicates to {n_phys} physical experts, "
                        f"which do not shard over the {self.n_ep}-device EP "
                        f"axis — plan with total_multiple={self.n_ep}")
            self.adopt_replication(rep)
        rounds = resolve_rounds(plan, self.n_ep)
        self.swap_rounds(rounds)
        return rounds

    def adopt_degraded(self, plan) -> None:
        """Adopt a survivor-only degraded ``Plan`` (``AuroraPlanner
        .plan_degraded``): rebuild the mesh over the surviving devices and
        carry every byte of serving state across.

        ``plan.survivors`` indexes the ORIGINAL flat EP device order (mesh
        device i == cluster device i). The rebuild pulls params (back to
        the logical frame), cache and the token buffer to host, constructs
        the survivor mesh from the surviving jax devices, re-shards the
        model over it, recomposes the step wrapper (the user's wrapper —
        stashed at construction — around the NEW mesh's context), refreshes
        the BvN rounds from the plan's degraded schedules, and re-adopts
        the plan's replication counts. Host state is bit-copied, so
        surviving requests' token streams are unchanged; requests resident
        on lost devices must be ``requeue``d by the caller (the
        ``ChaosHarness`` does both in order)."""
        survivors = getattr(plan, "survivors", None)
        if survivors is None:
            raise PlanError(
                "adopt_degraded needs a degraded Plan (built by "
                "AuroraPlanner.plan_degraded) — this plan has no "
                ".survivors device list")
        flat = list(self.mesh.devices.flat)
        n_old = len(flat)
        surv = [int(s) for s in survivors]
        if any(not 0 <= s < n_old for s in surv):
            raise PlanError(
                f"plan survivors {surv} do not index this mesh's "
                f"{n_old} devices")
        if self.n_ep != n_old:
            raise PlanError(
                "adopt_degraded needs the flat EP axis to cover the whole "
                f"mesh ({self.n_ep} EP devices over {n_old} mesh devices)")
        n_e = self.model.cfg.moe.n_experts
        if n_e % len(surv):
            raise PlanError(
                f"{n_e} experts do not shard over {len(surv)} survivors — "
                "plan with plan_degraded(ep_compatible=True) so the "
                "survivor subset divides the expert count")
        # Drop to the canonical logical frame through the tested
        # placement-only paths, then pull everything to host.
        if self.model.pc.moe_replication is not None:
            self.adopt_replication(None)
        if self.assignment is not None \
                and self.assignment != list(range(n_e)):
            self.adopt_assignment(list(range(n_e)))
        params = jax.tree_util.tree_map(np.asarray, self.params)
        cache = jax.tree_util.tree_map(np.asarray, self.cache)
        tokens = np.asarray(self.tokens)
        # Survivor mesh: same axis names, all-singleton leading axes, the
        # surviving devices (ascending original order) on the last.
        shape = tuple(1 for _ in self.mesh.axis_names[:-1]) + (len(surv),)
        mesh = jax.sharding.Mesh(
            np.array([flat[s] for s in surv]).reshape(shape),
            self.mesh.axis_names)
        model = distribute(self.model, mesh,
                           moe_impl=self.model.pc.moe_impl,
                           overlap=self.model.pc.ep_overlap)
        self.mesh = mesh
        self.n_ep = ep_size(model.pc)
        self._step_wrapper = _compose_wrapper(self._user_wrapper, mesh)
        if model.pc.moe_impl == "aurora":
            model = _with_rounds(model,
                                 resolve_rounds(plan, self.n_ep))
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.cache = jax.tree_util.tree_map(jnp.asarray, cache)
        self.tokens = jnp.asarray(tokens)
        self.assignment = list(range(n_e))
        self._rebind(model)
        self.adopt_replication(plan.replication)
        record_adoption(self._telemetry, "degraded", step=self.decode_steps,
                        survivors=surv)


class DistributedColocatedEngine(ColocatedContinuousEngine):
    """Aurora dual-model continuous serving, EP-sharded over a mesh.

    Both tenants' dispatch collectives run over the same flat EP axis inside
    one fused lockstep program. With ``replan=OnlineReplanner(...)`` the
    engine closes the full distributed loop: live in-collective routing
    counts → monitors → re-pairing, and every ADOPTED re-plan also refreshes
    the ppermute rounds from the plan's schedules (``refresh_rounds=False``
    opts out; the swap itself is placement-only either way).
    """

    def __init__(self, model_a: Model, model_b: Model, params_a, params_b,
                 batch_slots: int, cache_cap: int, *, mesh,
                 moe_impl: str = "aurora", rounds=None, plan=None,
                 overlap: bool = False, refresh_rounds: bool = True,
                 config: EngineConfig | None = None, **kw):
        config, self._user_wrapper = _mesh_config(
            config, kw, type(self).__name__, mesh)
        model_a = distribute(model_a, mesh, moe_impl=moe_impl,
                             overlap=overlap)
        model_b = distribute(model_b, mesh, moe_impl=moe_impl,
                             overlap=overlap)
        self.mesh = mesh
        self.n_ep = ep_size(model_a.pc)
        self.refresh_rounds = refresh_rounds
        rounds = _ctor_rounds(rounds, plan, self.n_ep)
        if rounds is not None:
            model_a, model_b = (_with_rounds(m, rounds)
                                for m in (model_a, model_b))
        if plan is not None and kw.get("pair") is None and plan.pair:
            kw["pair"] = list(plan.pair)
        super().__init__(model_a, model_b, params_a, params_b, batch_slots,
                         cache_cap, config=config, **kw)

    @property
    def rounds(self):
        return self.model_a.pc.aurora_rounds

    def swap_rounds(self, rounds) -> None:
        """Swap both tenants' ppermute schedules and rebuild the fused
        lockstep step — placement-only (see ``DistributedEngine``)."""
        _require_aurora(self.model_a.pc)
        rounds = validate_rounds_cover(rounds, self.n_ep)
        for pool in (self.pool_a, self.pool_b):
            pc = dataclasses.replace(pool.model.pc, aurora_rounds=rounds)
            pool._rebind(dataclasses.replace(pool.model, pc=pc))
        self.model_a, self.model_b = self.pool_a.model, self.pool_b.model
        self._build_lockstep()
        record_adoption(self._telemetry, "rounds", step=self.decode_steps,
                        n_rounds=len(rounds))

    def adopt(self, source):
        """One adoption surface for placement AND schedule: a full ``Plan``
        re-realizes its pairing on pool B (placement-only, via the shared
        ``reseat_pairing`` checkpoint) and then refreshes the ppermute
        rounds from its schedules; a ``MoETrace`` / traffic matrix refreshes
        rounds only. Returns the adopted rounds."""
        if hasattr(source, "schedules") and source.pair:
            ColocatedContinuousEngine.adopt(self, source)
        rounds = resolve_rounds(source, self.n_ep)
        self.swap_rounds(rounds)
        return rounds

    def _adopt_online(self, plan) -> None:
        ColocatedContinuousEngine.adopt(self, plan)
        if self.refresh_rounds and self.model_a.pc.moe_impl == "aurora":
            # The adopted plan was computed from the LIVE traces, so its
            # schedules already reflect current traffic under the new
            # pairing — exactly what the rounds should realize.
            self.swap_rounds(resolve_rounds(plan, self.n_ep))


class DistributedMultiTenantEngine(MultiTenantContinuousEngine):
    """N-tenant colocated continuous serving, EP-sharded over a mesh, with
    re-grouping-triggered rounds refresh (the N-way analogue of
    ``DistributedColocatedEngine``)."""

    def __init__(self, models: list[Model], params: list, batch_slots: int,
                 cache_cap: int, *, mesh, moe_impl: str = "aurora",
                 rounds=None, plan=None, overlap: bool = False,
                 refresh_rounds: bool = True,
                 config: EngineConfig | None = None, **kw):
        config, self._user_wrapper = _mesh_config(
            config, kw, type(self).__name__, mesh)
        models = [distribute(m, mesh, moe_impl=moe_impl, overlap=overlap)
                  for m in models]
        self.mesh = mesh
        self.n_ep = ep_size(models[0].pc)
        self.refresh_rounds = refresh_rounds
        rounds = _ctor_rounds(rounds, plan, self.n_ep)
        if rounds is not None:
            models = [_with_rounds(m, rounds) for m in models]
        if plan is not None and kw.get("groups") is None and plan.groups:
            kw["groups"] = [tuple(g) for g in plan.groups]
        super().__init__(models, params, batch_slots, cache_cap,
                         config=config, **kw)

    @property
    def rounds(self):
        return self.models[0].pc.aurora_rounds

    def swap_rounds(self, rounds) -> None:
        _require_aurora(self.models[0].pc)
        rounds = validate_rounds_cover(rounds, self.n_ep)
        for pool in self.pools:
            pc = dataclasses.replace(pool.model.pc, aurora_rounds=rounds)
            pool._rebind(dataclasses.replace(pool.model, pc=pc))
        self.models = [p.model for p in self.pools]
        self._build_lockstep()
        record_adoption(self._telemetry, "rounds", step=self.decode_steps,
                        n_rounds=len(rounds))

    def adopt(self, source):
        """One adoption surface: a full ``Plan`` re-seats every tenant to
        its grouping (placement-only) and refreshes the rounds; a
        ``MoETrace`` / traffic matrix refreshes rounds only. Returns the
        adopted rounds."""
        if hasattr(source, "schedules") and source.groups:
            MultiTenantContinuousEngine.adopt(self, source)
        rounds = resolve_rounds(source, self.n_ep)
        self.swap_rounds(rounds)
        return rounds

    def _adopt_online(self, plan) -> None:
        MultiTenantContinuousEngine.adopt(self, plan)
        if self.refresh_rounds and self.models[0].pc.moe_impl == "aurora":
            self.swap_rounds(resolve_rounds(plan, self.n_ep))
