"""Engine configuration + admission policies: the serving public API.

``EngineConfig`` is the one knob surface shared by every serving engine
(single, colocated, multi-tenant, and their EP-sharded distributed
variants). It absorbs what used to be a sprawl of per-engine constructor
keywords; engines now take ``Engine(model, params, batch_slots, cache_cap,
config=EngineConfig(...))``. The old keywords still work as deprecated
shims (``coerce_config`` folds them into an ``EngineConfig`` and emits a
``DeprecationWarning``) so downstream callers migrate on their own clock —
the repo itself is fully migrated and CI runs with
``-W error::DeprecationWarning``.

``AdmissionPolicy`` replaces the loose ``prefill_chunk`` /
``step_token_budget`` / ``bucket_policy`` trio with one object that decides
how queued prompts enter the slot pool (t2t's ``data_reader.py`` bucketing
schemes are the exemplar):

* ``FifoAdmission`` — one-shot admission in arrival order: a free slot
  absorbs the whole (bucketed) prompt in one prefill program.
* ``LengthBucketedAdmission`` — chunked admission: prompts are bucketed to
  a pad length and absorbed ``chunk`` tokens per engine step, so a long
  prompt never stalls the decode loop for more than one chunk.
* ``TokenBudgetAdmission`` — chunked admission under a per-step token
  budget: decode always runs and eats ``num_active`` tokens of the budget;
  prefill chunks only proceed on leftover budget.
* ``EdfAdmission`` — deadline-aware token-budget admission:
  earliest-deadline-first within the chunk budget, starvation-free via
  aging (``age_limit`` caps every request's effective deadline at
  ``arrival + age_limit``, so deadline-free traffic cannot be starved by a
  stream of tight deadlines). With ``shed=True`` it also REJECTS submits
  whose deadline is provably unattainable at current queue depth (or past
  ``queue_cap``) as typed ``ShedEvent`` results — overload robustness
  instead of silent queue growth.

Policies see the scheduler state as ``RequestSpec`` objects (arrival time,
prompt length, SLO deadline, tenant id, next chunk size) through two
methods: ``select(num_active, reqs)`` picks which due prefill chunks run
this engine step (in run order — deadline policies may reorder), and
``order(reqs)`` is the queue discipline for topping up the prefill pool.
Reordering is placement-only: each request's token stream depends only on
its own slot rows, so any admission order emits byte-identical tokens —
only TTFT/TPOT (the schedule) moves.

The pre-SLO protocol method — ``chunk_budget(num_active, chunks)`` over
bare chunk-size ints — remains as a deprecation shim mirroring
``coerce_config``: third-party policies that only implement it are wrapped
(one ``DeprecationWarning`` per config) into the ``select`` interface, and
the stock policies still answer ``chunk_budget`` calls (same warning) by
delegating to ``select``.

Per-tenant SLO targets are declared on ``EngineConfig.tenants`` as
``TenantSpec`` entries (p95 TTFT / p95 TPOT targets in engine-step units,
rate share of the step token budget, and — for the multi-tenant engine —
the tenant's model/params/pairing), which the engines translate into
per-request deadlines at ``submit`` time.

The legacy trio maps 1:1 onto the three original policies
(``resolve_admission``), so existing behavior is reproduced exactly — the
policy object is the same scheduler, named.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable, Protocol, Sequence


def make_bucketer(policy) -> Callable[[int], int]:
    """Resolve a prefill bucketing policy to ``fn(prompt_len) -> pad_len``.

    Policies:
      "pow2"     next power of two — few compiled prefill programs (default)
      "exact"    no padding — one compilation per distinct prompt length
      "step:K"   round up to a multiple of K — linear compile count, less pad
      callable   custom ``fn(n) -> >= n``
    """
    if callable(policy):
        return policy
    if policy == "pow2":
        def pow2(n: int) -> int:
            p = 1
            while p < n:
                p *= 2
            return p
        return pow2
    if policy == "exact":
        return lambda n: n
    if isinstance(policy, str) and policy.startswith("step:"):
        k = int(policy.split(":", 1)[1])
        if k <= 0:
            raise ValueError(f"bucket_policy 'step:K' needs a positive K, "
                             f"got {k}")
        return lambda n: -(-n // k) * k
    raise ValueError(f"bucket_policy {policy!r} is unknown "
                     "(expected 'pow2', 'exact', 'step:K', or a callable)")


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """What an admission policy sees about one pending request.

    ``chunk`` is the request's next due prefill chunk size in tokens (the
    whole padded prompt for one-shot admission, the first chunk for queue
    ordering); ``deadline`` is the absolute SLO deadline in engine-step
    time (``math.inf`` = no deadline); ``tenant`` is an opaque tenant id.
    """

    chunk: int
    prompt_len: int = 0
    arrival: float = 0.0
    deadline: float = math.inf
    tenant: object = None

    def __post_init__(self):
        if self.chunk < 0:
            raise ValueError("RequestSpec.chunk must be a non-negative "
                             "token count")
        if math.isnan(self.deadline):
            raise ValueError("RequestSpec.deadline must be a time or "
                             "math.inf, not NaN")


@dataclasses.dataclass(frozen=True)
class ShedEvent:
    """One rejected submit under shed-mode admission.

    Load shedding surfaces as a TYPED RESULT, never a silent stall or an
    exception: ``ContinuousEngine.submit`` returns the event (and appends
    it to ``engine.shed_events``) so callers — and per-tenant accounting —
    see exactly which request was refused and why. ``reason`` is
    human-readable and starts with the policy trigger (``"queue_cap"`` or
    ``"deadline"``)."""

    tenant: object
    arrival: float
    reason: str
    request: object = None


def _fifo_order(reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
    return tuple(range(len(reqs)))


def _deprecated_chunk_budget(policy, num_active: int,
                             chunks: Sequence[int]) -> int:
    warnings.warn(
        f"{type(policy).__name__}.chunk_budget(num_active, chunks) is "
        "deprecated — admission policies now expose select(num_active, "
        "reqs) over RequestSpec objects (repro.serving.RequestSpec)",
        DeprecationWarning, stacklevel=3)
    return len(policy.select(num_active,
                             [RequestSpec(chunk=int(c)) for c in chunks]))


class AdmissionPolicy(Protocol):
    """How queued prompts enter the slot pool.

    ``chunk`` is the per-step prefill granularity (None = one-shot whole
    prompts), ``budget`` the per-step token budget (None = unbudgeted);
    ``pad`` buckets a prompt length to its compiled pad length.

    ``select`` is the scheduler decision: given the decode load and the
    pending prefills' ``RequestSpec``s (arrival order), which of their due
    chunks run this step — returned as indices in run order, so a
    deadline-aware policy may reorder. ``order`` is the queue discipline:
    the priority order in which queued requests should enter the prefill
    pool. Both are placement-only decisions — any ordering emits identical
    token streams; only the schedule (TTFT/TPOT) changes.

    The old ``chunk_budget(num_active, chunks)`` int-based signature is
    deprecated; policies that only implement it are shimmed into ``select``
    with a ``DeprecationWarning`` (see ``coerce_admission``).
    """

    chunk: int | None
    budget: int | None

    def pad(self, prompt_len: int) -> int: ...

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]: ...

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]: ...


@dataclasses.dataclass(frozen=True)
class FifoAdmission:
    """One-shot admission in arrival order (no chunking): each free slot
    absorbs a whole bucketed prompt in one prefill program."""

    bucket_policy: object = "pow2"
    chunk = None
    budget = None

    def pad(self, prompt_len: int) -> int:
        return make_bucketer(self.bucket_policy)(prompt_len)

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)

    def chunk_budget(self, num_active: int, chunks: Sequence[int]) -> int:
        return _deprecated_chunk_budget(self, num_active, chunks)


@dataclasses.dataclass(frozen=True)
class LengthBucketedAdmission:
    """Chunked admission: prompts bucketed to a pad length and absorbed
    ``chunk`` tokens per engine step, unbudgeted (every in-flight prefill
    may advance one chunk per step)."""

    chunk: int
    bucket_policy: object = "pow2"
    budget = None

    def __post_init__(self):
        if self.chunk <= 0:
            raise ValueError("LengthBucketedAdmission.chunk must be a "
                             "positive token count")

    def pad(self, prompt_len: int) -> int:
        return make_bucketer(self.bucket_policy)(prompt_len)

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)

    def chunk_budget(self, num_active: int, chunks: Sequence[int]) -> int:
        return _deprecated_chunk_budget(self, num_active, chunks)


@dataclasses.dataclass(frozen=True)
class TokenBudgetAdmission:
    """Chunked admission under a per-step token budget.

    Decode always runs and eats ``num_active`` tokens of the budget; pending
    prefills advance in FIFO order on the leftover — the prefix of chunks
    whose sizes fit ``budget - num_active``. An empty pool bypasses the gate
    entirely (nothing is decoding, so there is nothing to protect), which is
    also the progress guarantee: decode drains slots, ``num_active`` falls,
    and the leftover eventually covers the head chunk.
    """

    chunk: int
    budget: int
    bucket_policy: object = "pow2"

    def __post_init__(self):
        if self.chunk <= 0:
            raise ValueError("TokenBudgetAdmission.chunk must be a "
                             "positive token count")
        if self.budget <= 0:
            raise ValueError("TokenBudgetAdmission.budget must be a "
                             "positive token count")

    def pad(self, prompt_len: int) -> int:
        return make_bucketer(self.bucket_policy)(prompt_len)

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        if num_active == 0:
            return _fifo_order(reqs)
        left = self.budget - num_active
        k = 0
        for r in reqs:
            if r.chunk > left:
                break
            left -= r.chunk
            k += 1
        return tuple(range(k))

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)

    def chunk_budget(self, num_active: int, chunks: Sequence[int]) -> int:
        return _deprecated_chunk_budget(self, num_active, chunks)


@dataclasses.dataclass(frozen=True)
class EdfAdmission:
    """Deadline-aware token-budget admission: earliest-deadline-first
    within the chunk budget, starvation-free via aging.

    Pending chunks are ranked by effective deadline
    ``min(deadline, arrival + age_limit)`` (ties broken by arrival, then
    submission order) — so a request with no SLO deadline competes as if
    due ``age_limit`` steps after it arrived, which bounds every request's
    wait behind tighter-deadline traffic (the aging guarantee: no
    starvation, however adversarial the deadline stream).

    Selection is WORK-CONSERVING: chunks are admitted greedily in deadline
    order while they fit ``budget - num_active``, and a chunk that does not
    fit is skipped rather than blocking later chunks that do — the engine
    never idles leftover budget while some due chunk would fit it. With
    ``budget=None`` every due chunk runs, in deadline order. The idle-engine
    bypass (``num_active == 0``) and the progress guarantee match
    ``TokenBudgetAdmission``.

    Reordering is placement-only: a request's tokens depend only on its own
    slot rows, so EDF emits byte-identical streams to FIFO — for a
    single-tenant stream with uniform deadlines even the schedule matches
    (the ranking degenerates to arrival order).

    **Shed mode** (``shed=True``): overloaded submits are REJECTED as typed
    ``ShedEvent`` results instead of queueing hopeless work. Two triggers,
    checked in order by ``shed_reason``: the queue already holds
    ``queue_cap`` requests, or the request's deadline is PROVABLY
    unattainable — even if prefill got the whole step budget every step,
    the prompt tokens queued at-or-ahead of it under EDF ranking could not
    finish before its deadline. The bound deliberately ignores decode's
    budget share and prompt padding, so it never sheds a request the
    engine might still serve in time; requests without a finite deadline
    are only ever capacity-shed. Shedding the provably-late tail is what
    keeps ADMITTED requests' TTFT inside their SLO under overload —
    without it, EDF ordering alone lets doomed work consume budget ahead
    of attainable deadlines.
    """

    chunk: int
    budget: int | None = None
    bucket_policy: object = "pow2"
    age_limit: float = 256.0
    shed: bool = False
    queue_cap: int | None = None

    def __post_init__(self):
        if self.chunk <= 0:
            raise ValueError("EdfAdmission.chunk must be a positive token "
                             "count")
        if self.budget is not None and self.budget <= 0:
            raise ValueError("EdfAdmission.budget must be a positive "
                             "token count")
        if not self.age_limit > 0:
            raise ValueError("EdfAdmission.age_limit must be a positive "
                             "step count (it is the starvation bound)")
        if self.queue_cap is not None and self.queue_cap < 1:
            raise ValueError("EdfAdmission.queue_cap must be >= 1 "
                             f"(got {self.queue_cap}); use None for "
                             "an unbounded queue")

    def pad(self, prompt_len: int) -> int:
        return make_bucketer(self.bucket_policy)(prompt_len)

    def _rank(self, reqs: Sequence[RequestSpec]) -> list[int]:
        key = lambda i: (min(reqs[i].deadline,
                             reqs[i].arrival + self.age_limit),
                         reqs[i].arrival, i)
        return sorted(range(len(reqs)), key=key)

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        ranked = self._rank(reqs)
        if self.budget is None or num_active == 0:
            return tuple(ranked)
        left = self.budget - num_active
        take = []
        for i in ranked:
            if reqs[i].chunk <= left:
                take.append(i)
                left -= reqs[i].chunk
        return tuple(take)

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return tuple(self._rank(reqs))

    def shed_reason(self, spec: RequestSpec,
                    queued: Sequence[RequestSpec],
                    num_active: int = 0) -> str | None:
        """Shed-mode admission test: the reason to reject ``spec`` given
        the current queue, or None to admit.

        The deadline trigger is a LOWER bound on time-to-first-token:
        prefill needs at least ``ceil(work / budget)`` engine steps, where
        ``work`` counts the new prompt plus every queued prompt ranked
        at-or-ahead of it under the EDF effective deadline. Decode's share
        of the budget, prompt padding, and slot contention are all ignored
        — each only makes reality slower — so a shed here is provable, not
        a heuristic. Unbudgeted policies only enforce ``queue_cap``."""
        if not self.shed:
            return None
        if self.queue_cap is not None and len(queued) >= self.queue_cap:
            return (f"queue_cap: {len(queued)} requests queued >= "
                    f"queue_cap {self.queue_cap}")
        if self.budget is None or not math.isfinite(spec.deadline):
            return None

        def eff(r: RequestSpec):
            return (min(r.deadline, r.arrival + self.age_limit), r.arrival)

        mine = eff(spec)
        work = spec.prompt_len + sum(
            r.prompt_len for r in queued if eff(r) <= mine)
        steps = math.ceil(work / self.budget)
        if spec.arrival + steps > spec.deadline:
            return (f"deadline: first token needs >= {steps} steps of the "
                    f"full prefill budget {self.budget} ({work} prompt "
                    "tokens at or ahead of this deadline), but the "
                    f"deadline is {spec.deadline - spec.arrival:g} steps "
                    "after arrival")
        return None

    def chunk_budget(self, num_active: int, chunks: Sequence[int]) -> int:
        return _deprecated_chunk_budget(self, num_active, chunks)


class _LegacyAdmission:
    """Deprecation shim for pre-``select`` admission policies (the old
    int-based ``chunk_budget`` protocol): adapts them to the ``select`` /
    ``order`` interface by forwarding bare chunk sizes and admitting the
    returned prefix. Created (with one ``DeprecationWarning``) by
    ``coerce_admission`` — mirroring ``coerce_config``'s legacy-kwarg
    shim."""

    def __init__(self, policy):
        self._policy = policy
        self.chunk = getattr(policy, "chunk", None)
        self.budget = getattr(policy, "budget", None)
        self.bucket_policy = getattr(policy, "bucket_policy", "pow2")

    def pad(self, prompt_len: int) -> int:
        return self._policy.pad(prompt_len)

    def select(self, num_active: int,
               reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        k = self._policy.chunk_budget(num_active, [r.chunk for r in reqs])
        return tuple(range(min(int(k), len(reqs))))

    def order(self, reqs: Sequence[RequestSpec]) -> tuple[int, ...]:
        return _fifo_order(reqs)


def coerce_admission(policy, owner: str = "EngineConfig"):
    """Adapt ``policy`` to the ``select``-based ``AdmissionPolicy`` protocol.

    Policies already speaking ``select`` pass through; legacy policies that
    only implement the deprecated int-based ``chunk_budget(num_active,
    chunks)`` are wrapped in ``_LegacyAdmission`` with a single
    ``DeprecationWarning`` (per call — ``EngineConfig.resolve_admission``
    caches the result, so an engine warns once)."""
    if hasattr(policy, "select"):
        return policy
    if hasattr(policy, "chunk_budget"):
        warnings.warn(
            f"{owner}: admission policy {type(policy).__name__} only "
            "implements the deprecated int-based chunk_budget(num_active, "
            "chunks) — implement select(num_active, reqs) over "
            "repro.serving.RequestSpec objects instead",
            DeprecationWarning, stacklevel=3)
        return _LegacyAdmission(policy)
    raise TypeError(
        f"{owner}: {type(policy).__name__} is not an admission policy "
        "(needs select(num_active, reqs) — see "
        "repro.serving.AdmissionPolicy)")


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's declaration: SLO targets plus (for the multi-tenant
    engine) its model, params, and expert pairing.

    SLO targets are in ENGINE-STEP time units (the same clock as
    ``Request.arrival``): ``ttft_p95`` is the p95 time-to-first-token
    target — engines turn it into per-request deadlines
    (``arrival + ttft_p95``) at submit time, which is what deadline-aware
    policies like ``EdfAdmission`` schedule against; ``tpot_p95`` is the
    p95 time-per-output-token target (reported by the SLO bench sweep, not
    a scheduling input). ``rate_share`` is the tenant's fraction of the
    step token budget — the multi-tenant engine scales a budgeted
    admission policy's ``budget`` by it, so one tenant's prefill burst
    cannot eat the whole step. Shares across one config must sum to <= 1.

    ``model``/``params``/``pair`` fold the multi-tenant constructor
    plumbing into the spec: ``MultiTenantContinuousEngine(batch_slots,
    cache_cap, config=EngineConfig(tenants=(TenantSpec(model=..,
    params=..), ...)))`` replaces the parallel models/params lists, and
    ``admit_tenant(TenantSpec(...))`` admits with the same validated type.
    ``params`` arrive in the LOGICAL (unpermuted) frame; ``pair`` is the
    slot->expert placement the engine realizes (identity when None).
    """

    name: str | None = None
    ttft_p95: float | None = None
    tpot_p95: float | None = None
    rate_share: float | None = None
    model: object = None
    params: object = None
    pair: tuple[int, ...] | None = None

    def __post_init__(self):
        for field in ("ttft_p95", "tpot_p95"):
            v = getattr(self, field)
            if v is not None and not v > 0:
                raise ValueError(f"{field} must be a positive engine-step "
                                 f"count, got {v!r}")
        if self.rate_share is not None and not 0 < self.rate_share <= 1:
            raise ValueError("rate_share must be in (0, 1] — it is the "
                             "tenant's fraction of the step token budget, "
                             f"got {self.rate_share!r}")
        if self.pair is not None:
            object.__setattr__(self, "pair",
                               tuple(int(x) for x in self.pair))
        if self.params is not None and self.model is None:
            raise ValueError("TenantSpec.params without model — the engine "
                             "needs both to host the tenant")

    def deadline(self, arrival: float) -> float:
        """Absolute SLO deadline for a request arriving at ``arrival``
        (``math.inf`` when the tenant declares no TTFT target)."""
        if self.ttft_p95 is None:
            return math.inf
        return arrival + self.ttft_p95


def scale_admission(policy, rate_share: float | None):
    """Per-tenant view of a budgeted admission policy: the tenant's pool
    gets ``budget * rate_share`` (floored at one chunk so progress is never
    configured away). Unbudgeted policies and ``None`` shares pass through
    unchanged."""
    budget = getattr(policy, "budget", None)
    if (rate_share is None or budget is None
            or not dataclasses.is_dataclass(policy)):
        return policy
    chunk = getattr(policy, "chunk", None) or 1
    return dataclasses.replace(
        policy, budget=max(int(chunk), int(round(budget * rate_share))))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Scheduling/compilation knobs shared by every serving engine.

    ``admission`` is the full-control path (any ``AdmissionPolicy``); the
    ``prefill_chunk``/``step_token_budget``/``bucket_policy`` fields are the
    shorthand that maps onto the three stock policies (and mirrors the old
    keyword API) — set one or the other, not both.

    ``prefill_pool = K`` admits up to K chunked prefills CONCURRENTLY: all
    their due chunks (and the decode step, in the single-model engine) run
    in ONE jitted program per engine step instead of one chunk per step.
    Each prompt is still absorbed as batch-1 sub-calls inside that program,
    so MoE capacity/drop semantics — computed per token group — are
    bit-identical to serialized admission and token streams cannot change;
    only the schedule (and the dispatch count) does. Requires chunked
    admission.

    ``kernels`` unifies kernel-path selection: ``False`` (dense reference),
    ``True`` (default ``KernelConfig``), or an explicit ``KernelConfig`` —
    one code path (``kernelize`` -> ``Model.with_kernels``, which also picks
    ``moe_impl="kernel"`` for non-EP MoE configs).

    ``step_wrapper`` wraps every compiled step (the distributed engines
    compose their mesh-context wrapper under it); ``jit=False`` runs steps
    eagerly (debugging).

    ``telemetry`` attaches a ``repro.serving.Telemetry`` hub: the
    engines' scheduling, dispatch, sampling and read-back become spans,
    shed/replan/fault/adoption events publish to the hub's bus, and the
    metrics registry fills in. ``None`` (default) costs one attribute
    test per span site and no per-step work. The hub is shared by colocated/multi-tenant pools (pool
    configs are ``dataclasses.replace`` copies). ``event_capacity``
    bounds the per-engine event rings (``shed_events``), drop-oldest.
    """

    prefill_len: int | None = None
    prefill_chunk: int | None = None
    step_token_budget: int | None = None
    bucket_policy: object = "pow2"
    prefill_pool: int = 1
    admission: AdmissionPolicy | None = None
    tenants: tuple[TenantSpec, ...] = ()
    kernels: object = False          # bool | KernelConfig
    jit: bool = True
    step_wrapper: Callable | None = None
    telemetry: object = None         # Telemetry | None
    event_capacity: int = 4096

    def __post_init__(self):
        object.__setattr__(self, "tenants", tuple(self.tenants))
        if self.event_capacity < 1:
            raise ValueError("event_capacity must be >= 1")
        for t in self.tenants:
            if not isinstance(t, TenantSpec):
                raise ValueError(f"tenants must be TenantSpec entries, "
                                 f"got {type(t).__name__}")
        shares = [t.rate_share for t in self.tenants
                  if t.rate_share is not None]
        if sum(shares) > 1 + 1e-9:
            raise ValueError(f"tenant rate_shares sum to {sum(shares)} > 1 "
                             "— shares are fractions of ONE step token "
                             "budget")
        if self.admission is not None:
            if (self.prefill_chunk is not None
                    or self.step_token_budget is not None):
                raise ValueError(
                    "admission= replaces the prefill_chunk/step_token_budget "
                    "shorthand — configure chunking inside the policy")
            if self.bucket_policy != "pow2":
                raise ValueError(
                    "with admission= set, pass bucket_policy inside the "
                    "admission policy (the config-level field would be "
                    "silently ignored)")
        if self.prefill_chunk is not None and self.prefill_chunk <= 0:
            raise ValueError("prefill_chunk must be a positive token count")
        if self.step_token_budget is not None and self.prefill_chunk is None:
            raise ValueError(
                "step_token_budget only gates CHUNKED prefill scheduling — "
                "one-shot admission absorbs whole prompts regardless; set "
                "prefill_chunk to give the budget something to schedule")
        if self.prefill_pool < 1:
            raise ValueError("prefill_pool must be >= 1")
        if self.prefill_pool > 1 and self.resolve_admission().chunk is None:
            raise ValueError(
                "prefill_pool > 1 pools CHUNKED prefills — one-shot "
                "admission has nothing to interleave; set prefill_chunk "
                "(or a chunked admission policy)")

    def resolve_admission(self) -> AdmissionPolicy:
        """The admission policy this config realizes (explicit ``admission``
        wins; else the legacy-trio mapping). Legacy ``chunk_budget``-only
        policies are shimmed to the ``select`` protocol here
        (``coerce_admission``), cached so the shim's single
        ``DeprecationWarning`` fires once per config."""
        cached = getattr(self, "_resolved_admission", None)
        if cached is not None:
            return cached
        if self.admission is not None:
            resolved = coerce_admission(self.admission)
        elif self.prefill_chunk is None:
            resolved = FifoAdmission(bucket_policy=self.bucket_policy)
        elif self.step_token_budget is None:
            resolved = LengthBucketedAdmission(
                chunk=self.prefill_chunk, bucket_policy=self.bucket_policy)
        else:
            resolved = TokenBudgetAdmission(
                chunk=self.prefill_chunk, budget=self.step_token_budget,
                bucket_policy=self.bucket_policy)
        object.__setattr__(self, "_resolved_admission", resolved)
        return resolved

    def kernelize(self, model):
        """The ONE kernel-selection code path: route ``model`` through the
        Pallas serving hot path per ``self.kernels`` (no-op when False;
        ``Model.with_kernels`` picks ``moe_impl`` for bool/KernelConfig)."""
        return model.with_kernels(self.kernels) if self.kernels else model


# Old per-engine constructor keywords, foldable 1:1 into EngineConfig.
_LEGACY_KEYS = ("prefill_len", "prefill_chunk", "step_token_budget",
                "bucket_policy", "kernels", "jit", "step_wrapper")


def coerce_config(config: EngineConfig | None, kwargs: dict, owner: str,
                  strict: bool = True) -> EngineConfig:
    """Deprecated-kwarg shim: pop legacy engine keywords out of ``kwargs``,
    fold them into an ``EngineConfig`` (with a ``DeprecationWarning``), and
    return the effective config.

    ``strict=True`` (the engine constructors) rejects any leftover key —
    the catch-all ``**legacy`` must not silently eat typos. The distributed
    engines pre-coerce with ``strict=False`` because their ``kwargs`` still
    carry real pass-through arguments (``monitor``, ``pair``, ...) for the
    parent constructor, which then runs the strict pass on what remains.
    """
    legacy = {k: kwargs.pop(k) for k in _LEGACY_KEYS if k in kwargs}
    if strict and kwargs:
        raise TypeError(f"{owner}: unexpected keyword argument(s) "
                        f"{sorted(kwargs)}")
    if not legacy:
        return config if config is not None else EngineConfig()
    if config is not None:
        raise ValueError(
            f"{owner}: pass either config=EngineConfig(...) or the "
            f"deprecated keyword(s) {sorted(legacy)}, not both")
    warnings.warn(
        f"{owner}({', '.join(sorted(legacy))}=...) is deprecated — pass "
        "config=EngineConfig(...) (repro.serving.EngineConfig)",
        DeprecationWarning, stacklevel=3)
    return EngineConfig(**legacy)
