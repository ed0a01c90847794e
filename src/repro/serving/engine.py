"""Serving engines: static fixed-batch and continuous batching.

``ServingEngine`` (the original) runs one fixed-shape batch to completion:
requests are left-padded to a common prompt length, and the whole batch
decodes for ``max(max_new_tokens)`` steps — throughput stalls on the longest
request, and nothing can start until the batch is done.

``ContinuousEngine`` owns a request queue plus ``batch_slots`` decode slots
over a shared, donated KV/SSM cache with **per-slot lengths**
(``init_cache(per_slot_len=True)``). Each step the scheduler admits queued
requests into free slots — a per-slot prefill writes one request's state into
its slot row (``Model.prefill_slot``) — then decodes every slot in one jitted
step and evicts finished requests, so a short request's slot is immediately
reusable while long requests keep decoding. Same math as the static engine
(per-row attention masking via the per-slot length vector), different
schedule.

All scheduling/compilation knobs arrive through one frozen ``EngineConfig``
(``repro.serving.config``): ``Engine(model, params, batch_slots, cache_cap,
config=EngineConfig(...))``. The old per-engine keywords remain as
deprecated shims.

**Chunked prefill** (``EngineConfig(prefill_chunk=C)``): instead of
absorbing a whole prompt in one admission step — stalling every active
slot's decode behind a long prefill — the prompt is consumed ``C`` tokens
per engine step straight into its slot's row of the shared cache
(``Model.prefill_chunk_slot``: slice, continue, merge in one donated
program). Between chunks the decode step freezes the pending slot's row
(``row_mask``), so the partial state survives interleaved decodes. An
``AdmissionPolicy`` decides which pending chunks run each step via
``select`` over per-request ``RequestSpec``s (arrival, prompt length, SLO
deadline, tenant): decode always runs; under ``TokenBudgetAdmission``
leftover budget feeds the FIFO prefix of due chunks, under
``EdfAdmission`` the earliest effective deadlines go first. Token streams
are identical to one-shot admission regardless of order (prefill
continuation is exact — see ``models.transformer.forward``); only the
schedule changes.

**Prefill pool** (``EngineConfig(prefill_pool=K)``): up to K chunked
prefills live in flight at once, and every engine step runs ALL their due
chunks plus the decode step as ONE jitted program — prefill effectively
overlaps decode by sharing its dispatch instead of serializing admission
one chunk per step. Each prompt still advances as batch-1 sub-calls inside
that program, so MoE capacity/drop semantics (computed per token group)
are bit-identical to serialized admission; completed prompts merge into
their reserved slots as they finish.

**Live routing stats** (``monitor=TrafficMonitor(...)``): decode steps and
prefills report per-layer expert routing counts, feeding the traffic-driven
re-planner (``repro.serving.monitor``).

**Kernel path** (``EngineConfig(kernels=True)`` or a ``KernelConfig``): the
engine's jitted steps run through the Pallas serving hot path — sort-based
ragged MoE dispatch into the fused grouped FFN and flash-decode attention
over the per-slot cache (``EngineConfig.kernelize`` ->
``Model.with_kernels``, the one kernel-selection path). Same
routing/capacity semantics, so token streams match the dense path; routing
counts still flow to the monitor (derived from the routing output by the
shared ``routed_counts`` scatter, no one-hot).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import FaultError, PlanError
from repro.models import Model
from repro.serving.config import (EngineConfig, RequestSpec, ShedEvent,
                                  coerce_config, make_bucketer)
from repro.serving.events import RingBuffer
from repro.serving.telemetry import _NULL_SPAN, STEP_BOUNDS, record_adoption

__all__ = ["Request", "poisson_requests", "serve_stream", "make_bucketer",
           "ServingEngine", "ContinuousEngine"]


@dataclasses.dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int = 16
    arrival: float = 0.0                 # engine-step time of arrival
    # Absolute SLO deadline (engine-step time) fed to deadline-aware
    # admission policies. None = derive from the engine's TenantSpec at
    # submit (math.inf when the tenant declares no TTFT target).
    deadline: float | None = None
    tenant: object = None                # opaque tenant id for the policy
    out_tokens: list = dataclasses.field(default_factory=list)
    # Process-unique id: every telemetry span of this request carries it.
    rid: int = dataclasses.field(default_factory=itertools.count().__next__,
                                 compare=False)


def poisson_requests(rng, n: int, rate: float, vocab: int, prompt_len: int,
                     max_new_lo: int, max_new_hi: int) -> list[Request]:
    """n requests with Exp(1/rate) inter-arrival gaps (a Poisson process,
    in decode-step time units) and uniform output lengths in
    [max_new_lo, max_new_hi]."""
    t = 0.0
    reqs = []
    for _ in range(n):
        t += float(rng.exponential(1.0 / rate)) if rate > 0 else 0.0
        reqs.append(Request(
            prompt=list(rng.integers(1, vocab, prompt_len)),
            max_new_tokens=int(rng.integers(max_new_lo, max_new_hi + 1)),
            arrival=t))
    return reqs


def serve_stream(step_fn, pools) -> None:
    """Arrival-clock driver shared by the continuous engines.

    ``pools``: (engine, requests) pairs — one for the single-model engine,
    two (lockstep) for the colocated engine. Each tick admits every request
    whose ``arrival`` has passed (same-arrival requests in list order), runs
    one ``step_fn()``, and jumps the clock over idle gaps when nothing is
    active but requests are still due.
    """
    streams = [[eng, sorted(reqs, key=lambda r: r.arrival), 0]
               for eng, reqs in pools]
    t = 0.0
    while any(i < len(p) or e.queue or e.num_active or e.num_pending
              for e, p, i in streams):
        for s in streams:
            eng, pend, i = s
            while i < len(pend) and pend[i].arrival <= t:
                eng.submit(pend[i])
                i += 1
            s[2] = i
        due = [p[i].arrival for _, p, i in streams if i < len(p)]
        if not step_fn() and due:
            t = max(t + 1.0, min(due))               # jump idle gaps
        else:
            t += 1.0


def _chunk_attrs(r: Request, slot: int, total: int, done: int,
                 c: int) -> dict:
    """``prefill_chunk`` span attributes for padded positions [done,
    done + c) of a ``total``-token left-padded prompt: the real prompt
    tokens it holds, the first one's position in the prompt, and whether
    the chunk ends the prompt."""
    pad = total - len(r.prompt)
    lo, hi = max(done, pad), max(done + c, pad)
    return {"rid": r.rid, "slot": slot, "real": hi - lo, "start": lo - pad,
            "last": done + c >= total}


class ServingEngine:
    def __init__(self, model: Model, params, batch_slots: int,
                 cache_cap: int, src_len: int = 0, jit: bool = True):
        self.model = model
        self.params = params
        self.batch_slots = batch_slots
        self.cache_cap = cache_cap
        self.src_len = src_len
        # Cache buffers are donated: the update aliases in place instead of
        # copying the full KV/SSM state every step.
        self._prefill = (jax.jit(model.prefill, donate_argnums=(2,))
                         if jit else model.prefill)
        self._decode = (jax.jit(model.decode_step, donate_argnums=(2,))
                        if jit else model.decode_step)
        self.decode_steps = 0            # decode invocations (for benchmarks)

    def _pad_prompts(self, reqs: list[Request]) -> np.ndarray:
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((self.batch_slots, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad with 0
        return toks

    def serve(self, reqs: list[Request], frames=None) -> list[Request]:
        """Run one batch of requests to completion (greedy decoding)."""
        if len(reqs) > self.batch_slots:
            raise ValueError("too many requests for the batch")
        toks = self._pad_prompts(reqs)
        cache = self.model.init_cache(self.batch_slots, self.cache_cap,
                                      src_len=self.src_len)
        inputs = {"tokens": jnp.asarray(toks)}
        if frames is not None:
            inputs["frames"] = jnp.asarray(frames)
        logits, cache = self._prefill(self.params, inputs, cache)
        tok = jnp.argmax(logits[:, -1:, : self.model.cfg.vocab],
                         axis=-1).astype(jnp.int32)
        steps = max(r.max_new_tokens for r in reqs)
        for _ in range(steps):
            for i, r in enumerate(reqs):
                if len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(tok[i, 0]))
            logits, cache = self._decode(self.params, tok, cache)
            self.decode_steps += 1
            tok = jnp.argmax(logits[:, :, : self.model.cfg.vocab],
                             axis=-1).astype(jnp.int32)
        return reqs


class ContinuousEngine:
    """Continuous-batching scheduler over ``batch_slots`` decode slots.

    ``prefill_len``: fixed left-pad length for per-slot prefills (one compiled
    prefill program). ``None`` buckets each prompt to the next power of two
    (one compilation per bucket). A prompt padded to length P behaves exactly
    like the static engine's batch padded to P, so outputs are
    token-identical when the pad lengths agree.

    The slot state machine lives host-side (``queue`` + ``slots``); device
    state is the shared cache (per-slot lengths) and the (B, 1) current-token
    buffer. Free slots keep decoding garbage rows — attention is batch-row
    independent and the rows are overwritten at the next admission — so the
    decode step is one fixed-shape jitted program regardless of occupancy.
    """

    def __init__(self, model: Model, params, batch_slots: int,
                 cache_cap: int, src_len: int = 0,
                 config: EngineConfig | None = None, monitor=None,
                 **legacy):
        config = coerce_config(config, legacy, type(self).__name__)
        self.config = config
        model = config.kernelize(model)
        self.model = model
        self.params = params
        self.batch_slots = batch_slots
        self.cache_cap = cache_cap
        self.src_len = src_len
        self.admission = config.resolve_admission()
        # The single-model engine hosts ONE tenant: its spec (SLO targets)
        # turns into per-request deadlines at submit. The colocated /
        # multi-tenant engines split their config's tenants across pools.
        if len(config.tenants) > 1:
            raise ValueError(
                f"{type(self).__name__} hosts one tenant; "
                f"config.tenants has {len(config.tenants)} — use "
                "MultiTenantContinuousEngine for several")
        self.tenant_spec = config.tenants[0] if config.tenants else None
        # Derived views kept for callers that inspected the old attributes.
        self.prefill_len = config.prefill_len
        self.prefill_chunk = self.admission.chunk
        self.step_token_budget = self.admission.budget
        self._bucketer = make_bucketer(self.admission.bucket_policy)
        self._pool_size = config.prefill_pool
        self.monitor = monitor
        self.cache = model.init_cache(batch_slots, cache_cap,
                                      src_len=src_len, per_slot_len=True)
        self.tokens = jnp.zeros((batch_slots, 1), jnp.int32)
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Request | None] = [None] * batch_slots
        # In-flight chunked prefills, arrival order: [req, slot,
        # padded_toks, done]. The admission policy's select() picks which
        # of their due chunks run each step (deadline policies reorder).
        self._pending: list[list] = []
        # Exclusive-scenario expert->device assignment REALIZED in params
        # (identity unless an exclusive plan was adopted); None = non-MoE.
        self.assignment = (list(range(model.cfg.moe.n_experts))
                          if model.cfg.moe is not None else None)
        self._jit = config.jit
        # Distributed engines wrap every compiled step so it runs under the
        # mesh context (bare ``PartitionSpec`` sharding constraints resolve
        # against it); identity for the single-device engines.
        self._step_wrapper = config.step_wrapper or (lambda fn: fn)
        # Optional telemetry hub (``config.telemetry``): spans at the
        # scheduler's boundaries (``step``'s span tree) plus shed/adoption
        # events and queue/TTFT/token metrics. None (default) costs one
        # attribute test per span site.
        self._telemetry = config.telemetry
        self._tenant_label = (self.tenant_spec.name
                              if self.tenant_spec is not None else "")
        self._build_steps()
        self.decode_steps = 0
        # Shed-mode admission: every rejected submit is recorded here as a
        # typed ``ShedEvent`` (and returned from ``submit``) — rejections
        # are observable per tenant, never silent stalls. Bounded ring
        # (``config.event_capacity``), drop-oldest; evictions are counted
        # on ``shed_events.dropped``.
        self.shed_events: RingBuffer = RingBuffer(config.event_capacity)

    @property
    def telemetry(self):
        """The attached ``Telemetry`` hub, or None. Spans are taken at the
        call sites from this attribute, so a hub set on a live engine
        records from the next step on, and setting None stops it."""
        return self._telemetry

    @telemetry.setter
    def telemetry(self, hub) -> None:
        self._telemetry = hub

    def _build_steps(self) -> None:
        """(Re)build the jitted step programs from ``self.model``, each
        under the configured ``step_wrapper`` (mesh context / fault
        injection). The programs are named functions, so a device trace
        shows ``jit_prefill_slot``, ``jit_prefill_chunk_first``,
        ``jit_prefill_chunk`` and ``jit_decode_step``."""
        model, jit, wrap = self.model, self._jit, self._step_wrapper
        stats = self.monitor is not None
        cap, src_len = self.cache_cap, self.src_len

        def prefill_slot(params, inputs, cache, slot):
            return model.prefill_slot(params, inputs, cache, slot, cap=cap,
                                      src_len=src_len,
                                      collect_moe_stats=stats)

        # Chunked prefill runs straight against the shared per-slot cache:
        # each chunk slices the slot row, continues the prefill, and merges
        # back in ONE donated program (``Model.prefill_chunk_slot``) — no
        # detached batch-1 cache lives on the host between chunks.
        def prefill_chunk_first(params, inputs, cache, slot):
            return model.prefill_chunk_slot(
                params, inputs, cache, slot, first=True, cap=cap,
                src_len=src_len, collect_moe_stats=stats)

        def prefill_chunk(params, inputs, cache, slot):
            return model.prefill_chunk_slot(
                params, inputs, cache, slot, first=False, cap=cap,
                src_len=src_len, collect_moe_stats=stats)

        def compiled(fn, **kw):
            return wrap(jax.jit(fn, **kw) if jit else fn)

        self._prefill = compiled(prefill_slot, donate_argnums=(2,))
        self._chunk_first = compiled(prefill_chunk_first, donate_argnums=(2,))
        self._chunk = compiled(prefill_chunk, donate_argnums=(2,))
        self._decode = compiled(
            model.decode_step_stats if stats else model.decode_step,
            donate_argnums=(2,))
        if self._pool_size > 1:
            self._pool_step = compiled(self._make_pool_fn(stats),
                                       static_argnums=(0, 1),
                                       donate_argnums=(4,))

    def _make_pool_fn(self, stats: bool):
        """The pooled-admission program: K chunked prefills (and, when
        ``decode`` is set, the decode step over all slots) threaded through
        the shared donated cache in ONE jitted function.

        Each prefill stays a batch-1 ``prefill_chunk_slot`` sub-call — MoE
        capacity and dispatch ranks are computed per token group, so
        batching the K chunks into one (K, C) group would route with K*C
        tokens of rank competition and break token identity with serialized
        admission. Composing the sub-calls keeps the math bit-identical
        while XLA fuses/schedules them as one program (one dispatch per
        engine step instead of up to K+1).

        ``firsts`` (per-chunk fresh-slot flags) and ``decode`` are static:
        the program retraces per (pool shape, firsts, decode) combination,
        bounded in practice by the chunk bucketing.
        """
        model = self.model
        chunk = partial(model.prefill_chunk_slot, cap=self.cache_cap,
                        src_len=self.src_len, collect_moe_stats=stats)
        dec = model.decode_step_stats if stats else model.decode_step

        def pool_fn(firsts, decode, params, toks, cache, slots, tokens,
                    mask):
            chunk_out = []
            for inp, slot, first in zip(toks, slots, firsts):
                out = chunk(params, inp, cache, slot, first=first)
                if stats:
                    logits, cache, st = out
                else:
                    (logits, cache), st = out, None
                chunk_out.append((logits, st))
            dec_out = None
            if decode:
                out = dec(params, tokens, cache, mask)
                if stats:
                    logits, cache, st = out
                else:
                    (logits, cache), st = out, None
                dec_out = (logits, st)
            return chunk_out, dec_out, cache

        return pool_fn

    def _rebind(self, model: Model) -> None:
        """Swap the model (e.g. a ``ParallelContext`` with fresh ppermute
        rounds) and rebuild the jitted steps. Serving state — cache, slots,
        queue, in-flight prefill — is untouched: a rebind mid-stream is
        placement-only as long as the new model computes the same function."""
        self.model = model
        self._build_steps()

    def _set_replication(self, spec) -> None:
        """Install a hot-expert ``ReplicationSpec`` (placement-only).

        De-replicates the current expert leaves back to the logical frame,
        widens them under the new spec (pure copies of their home experts),
        and rebinds with ``pc.moe_replication`` updated. Routing, capacity
        and drops all stay in the logical frame (the shard-of-token rule in
        ``models.moe``), so a mid-stream swap cannot change emitted tokens."""
        from repro.models.moe import (dereplicate_moe_params,
                                      replicate_moe_params)
        cur = self.model.pc.moe_replication
        if spec is not None and spec.is_identity:
            spec = None
        if (None if cur is None else cur.counts) == \
                (None if spec is None else spec.counts):
            return
        params = self.params
        if cur is not None:
            params = dereplicate_moe_params(params, cur)
        if spec is not None:
            params = replicate_moe_params(params, spec)
        self.params = params
        pc = dataclasses.replace(self.model.pc, moe_replication=spec)
        self._rebind(dataclasses.replace(self.model, pc=pc))
        record_adoption(self._telemetry, "replication",
                        step=self.decode_steps,
                        counts=None if spec is None else spec.counts)

    def adopt_replication(self, replication) -> None:
        """Adopt a planner host map (``Plan.replication`` — per-expert host
        tuples — or a bare per-expert copy-count sequence). ``None`` or the
        identity map drops back to unreplicated serving."""
        from repro.models.moe import ReplicationSpec
        if replication is None:
            spec = None
        else:
            counts = tuple(
                len(h) if hasattr(h, "__len__") else int(h)
                for h in replication)
            spec = ReplicationSpec.from_counts(counts)
        self._set_replication(spec)

    def adopt_assignment(self, expert_to_device) -> None:
        """Adopt an exclusive-scenario expert->GPU assignment (Thm 5.1)
        placement-only: device slot d's expert leaves are re-seated so
        expert e sits on ``expert_to_device[e]``, and the router columns
        follow (``reseat_pairing``), so the composed function — and every
        emitted token — is unchanged. The monitor's stats frame is updated
        to the new slot->expert map.

        In this engine "device slot" is a position along the expert axis —
        exactly how EP sharding places contiguous expert blocks, so the
        same adoption is a REAL device move under ``DistributedEngine``."""
        from repro.serving.colocated import inverse_pair, reseat_pairing
        if self.assignment is None:
            raise PlanError("adopt_assignment needs an MoE model "
                            "(expert->device assignment is per expert)")
        e2d = [int(x) for x in np.asarray(expert_to_device).tolist()]
        n_e = len(self.assignment)
        if sorted(e2d) != list(range(n_e)):
            raise PlanError(
                f"expert_to_device {e2d} is not a permutation of "
                f"0..{n_e - 1} — exclusive assignment places one expert "
                "per device")
        if e2d == self.assignment:
            return
        if self.model.pc.moe_replication is not None:
            raise PlanError(
                "cannot re-seat an expert assignment while replicas are "
                "live — adopt_replication(None) first (the replicated "
                "leaves are in the widened physical frame)")
        old_pair = inverse_pair(self.assignment)   # device slot -> expert
        new_pair = inverse_pair(e2d)
        self.params = reseat_pairing(self.params, old_pair, new_pair,
                                     self.model.cfg)
        self.assignment = e2d
        if self.monitor is not None:
            self.monitor.slot_to_expert = new_pair
        record_adoption(self._telemetry, "assignment",
                        step=self.decode_steps, expert_to_device=e2d)

    def adopt(self, plan) -> None:
        """Unified adoption surface (one verb across every engine): take
        whatever placement evidence the caller has and re-realize it
        placement-only, mid-stream. For the single-model engine that is a
        full exclusive-scenario ``Plan`` (its ``.expert_to_device``
        assignment and/or ``.replication`` host map), a bare per-expert
        host-map/copy-count sequence, or ``None`` to drop back to
        unreplicated serving. The colocated/multi-tenant engines extend
        this verb to pairing/grouping, the distributed engines to Aurora
        round refresh."""
        if not hasattr(plan, "schedules"):
            self.adopt_replication(plan)
            return
        if (plan.pair is None and plan.groups is None
                and plan.replication is None and self.assignment is not None
                and len(plan.expert_to_device) == len(self.assignment)):
            self.adopt_assignment(plan.expert_to_device)
        self.adopt_replication(plan.replication)

    # -- scheduler ---------------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def num_pending(self) -> int:
        """In-flight chunked prefills (up to ``config.prefill_pool``)."""
        return len(self._pending)

    def submit(self, req: Request) -> ShedEvent | None:
        # Final per-slot length is pad(prompt) + max_new_tokens - 1 (the
        # last emitted token is never written back); beyond cache_cap the
        # decode path would silently overwrite slot cap-1 every step.
        p = self._bucket(len(req.prompt))
        need = p + max(req.max_new_tokens - 1, 0)
        if need > self.cache_cap:
            raise ValueError(
                f"prompt + generation needs {need} cache slots, "
                f"capacity is {self.cache_cap}")
        if (self.prefill_chunk is not None
                and not self.model.supports_chunked_prefill(
                    p, self.cache_cap)):
            raise ValueError(
                f"{self.model.cfg.arch_id}: a {p}-token prefill cannot be "
                "chunked (MLA / encoder-decoder, or a prompt that WRAPS "
                "the sliding-window ring — prompts inside the ring chunk "
                "fine) — use prefill_chunk=None for this engine")
        if req.deadline is None:
            # Per-request deadlines default from the tenant's SLO target
            # (TenantSpec.ttft_p95); no tenant or no target = no deadline.
            req.deadline = (self.tenant_spec.deadline(req.arrival)
                            if self.tenant_spec is not None else math.inf)
        if req.tenant is None and self.tenant_spec is not None:
            req.tenant = self.tenant_spec.name
        # Shed-mode admission (``EdfAdmission(shed=True)``): reject — as a
        # typed result, not an exception — when the queue is capped out or
        # the deadline is provably unattainable at current queue depth.
        shed_reason = getattr(self.admission, "shed_reason", None)
        if shed_reason is not None:
            def spec_of(r):
                b = self._bucket(len(r.prompt))
                return self._spec(r, min(self.prefill_chunk or b, b))
            reason = shed_reason(spec_of(req),
                                 [spec_of(r) for r in self.queue],
                                 self.num_active + self.num_pending)
            if reason is not None:
                ev = ShedEvent(tenant=req.tenant, arrival=req.arrival,
                               reason=reason, request=req)
                self.shed_events.append(ev)
                tel = self._telemetry
                if tel is not None and tel.enabled:
                    tel.count("serving_sheds_total",
                              help="submits rejected by shed-mode admission",
                              tenant=str(req.tenant), reason=reason)
                    tel.publish("shed", ev, step=self.decode_steps)
                return ev
        self.queue.append(req)
        return None

    def _bucket(self, n: int) -> int:
        if self.prefill_len is not None:
            if n > self.prefill_len:
                raise ValueError(f"prompt len {n} > prefill_len "
                                 f"{self.prefill_len}")
            return self.prefill_len
        p = self._bucketer(n)
        if p < n:
            raise ValueError(f"bucket policy shrank {n} to {p}")
        p = min(p, self.cache_cap)
        if self.prefill_chunk is not None:
            # A pow2/step pad can push a prompt that FITS a sliding-window
            # ring past it (e.g. 10 tokens padded to 16 over a 12-ring) and
            # trigger the wrapped-ring refusal; clamp the pad to the ring so
            # only genuinely wrapping prompts are refused. Applied in
            # _bucket so submit and admission agree on the padded length.
            lim = self.model.chunkable_len(self.cache_cap)
            if lim is not None and n <= lim:
                p = min(p, lim)
        return p

    def _free_slot(self) -> int | None:
        """First free slot not reserved by an in-flight prefill."""
        reserved = {p[1] for p in self._pending}
        for i, r in enumerate(self.slots):
            if r is None and i not in reserved:
                return i
        return None

    def _spec(self, r: Request, chunk: int) -> RequestSpec:
        """The admission policy's view of one pending request."""
        return RequestSpec(
            chunk=int(chunk), prompt_len=len(r.prompt), arrival=r.arrival,
            deadline=math.inf if r.deadline is None else r.deadline,
            tenant=r.tenant)

    @staticmethod
    def _check_selection(order, n: int) -> list[int]:
        """Sanitize a policy's select()/order() result: indices must be
        unique and in range (a buggy policy would otherwise run the same
        chunk twice against the donated cache)."""
        idx = [int(i) for i in order]
        if len(set(idx)) != len(idx) or any(not 0 <= i < n for i in idx):
            raise ValueError(
                f"admission policy returned invalid indices {idx} for "
                f"{n} pending requests (need unique ints in range)")
        return idx

    def _pop_queue(self) -> Request:
        """Next queued request per the policy's queue discipline
        (``order`` — FIFO for the stock policies, earliest effective
        deadline for ``EdfAdmission``)."""
        if len(self.queue) > 1:
            specs = [self._spec(r, min(self.prefill_chunk
                                       or self._bucket(len(r.prompt)),
                                       self._bucket(len(r.prompt))))
                     for r in self.queue]
            order = self._check_selection(self.admission.order(specs),
                                          len(specs))
            if order:
                r = self.queue[order[0]]
                del self.queue[order[0]]
                return r
        return self.queue.popleft()

    def _finish_admission(self, r: Request, slot: int, logits) -> None:
        """Shared tail of one-shot and chunked admission: emit the first
        token and occupy the slot (unless the request is already done)."""
        tel = self._telemetry
        with (_NULL_SPAN if tel is None
              else tel.span("first_token", rid=r.rid, slot=slot)):
            tok0 = int(jnp.argmax(logits[0, -1, : self.model.cfg.vocab]))
            if r.max_new_tokens > 0:
                r.out_tokens.append(tok0)
            if len(r.out_tokens) < r.max_new_tokens:
                self.slots[slot] = r
                self.tokens = self.tokens.at[slot, 0].set(tok0)
        if tel is not None and tel.enabled and r.max_new_tokens > 0:
            tel.count("serving_tokens_total",
                      help="tokens emitted", tenant=self._tenant_label)
            tel.observe("serving_ttft_steps",
                        max(0.0, self.decode_steps - r.arrival),
                        help="engine steps from arrival to first token "
                             "(step clock)",
                        bounds=STEP_BOUNDS, tenant=self._tenant_label)

    def _admit(self) -> None:
        """Drain the queue into free slots (one-shot per-slot prefill each,
        in the policy's queue order)."""
        tel = self._telemetry
        while self.queue and None in self.slots:
            with _NULL_SPAN if tel is None else tel.span("admit"):
                slot = self.slots.index(None)
                r = self._pop_queue()
                p = self._bucket(len(r.prompt))
                toks = np.zeros((1, p), np.int32)
                toks[0, p - len(r.prompt):] = r.prompt  # left-pad with 0
                inputs = {"tokens": jnp.asarray(toks)}
            with (_NULL_SPAN if tel is None else tel.span(
                    "prefill", rid=r.rid, slot=slot, real=len(r.prompt))):
                out = self._prefill(self.params, inputs, self.cache,
                                    jnp.int32(slot))
            if self.monitor is not None:
                logits, self.cache, stats = out
                self._observe_prefill(stats, pad=p - len(r.prompt))
            else:
                logits, self.cache = out
            self._finish_admission(r, slot, logits)

    def _admit_tick(self) -> bool:
        """One scheduler tick of admission work. Returns True iff chunked
        prefill progressed (one-shot admissions surface via num_active)."""
        if self.prefill_chunk is None:
            self._admit()
            return False
        if self._pool_size > 1:
            return self._pool_tick(fuse_decode=False)
        return self._prefill_tick()

    def _start_pending(self, slot: int) -> None:
        """Pop the policy's next queued request into a reserved slot as an
        in-flight prefill."""
        r = self._pop_queue()
        p = self._bucket(len(r.prompt))
        toks = np.zeros((1, p), np.int32)
        toks[0, p - len(r.prompt):] = r.prompt          # left-pad with 0
        self._pending.append([r, slot, toks, 0])

    def _prefill_tick(self) -> bool:
        """Serialized chunked admission (``prefill_pool=1``): start or
        advance the single in-flight prefill by at most one
        ``prefill_chunk``-token chunk, as the admission policy allows. Every
        chunk lands directly in the slot's row of the shared cache; between
        chunks the decode step freezes that row (``row_mask``), so the
        partial state survives interleaved decode ticks untouched."""
        tel = self._telemetry
        with _NULL_SPAN if tel is None else tel.span("admit"):
            if not self._pending:
                slot = self._free_slot()
                if not self.queue or slot is None:
                    return False
                self._start_pending(slot)
            r, slot, toks, done = self._pending[0]
            c = min(self.prefill_chunk, toks.shape[1] - done)
            # Decode always runs and eats num_active tokens of any budget;
            # the chunk only proceeds when the policy admits it. Progress
            # is guaranteed: decode drains slots, so num_active falls and
            # the leftover eventually covers a chunk (or the pool empties
            # and the budget gate is bypassed entirely).
            if not self.admission.select(self.num_active,
                                         [self._spec(r, c)]):
                return False
            chunk_toks = {"tokens": jnp.asarray(toks[:, done:done + c])}
        # The first chunk starts the slot from a fresh zero state (no
        # leakage from the previous occupant); later chunks resume from the
        # slot's own recorded fill level.
        fn = self._chunk_first if done == 0 else self._chunk
        with (_NULL_SPAN if tel is None else tel.span(
                "prefill_chunk", **_chunk_attrs(r, slot, toks.shape[1],
                                                done, c))):
            out = fn(self.params, chunk_toks, self.cache, jnp.int32(slot))
        if self.monitor is not None:
            logits, self.cache, stats = out
            # The chunk covers padded positions [done, done+c); left-pad
            # spans [0, total - len(prompt)) of the padded prompt.
            self._observe_prefill(
                stats, pad=(toks.shape[1] - len(r.prompt)) - done)
        else:
            logits, self.cache = out
        done += c
        if done < toks.shape[1]:
            self._pending[0][3] = done
            return True
        self._pending.pop(0)
        self._finish_admission(r, slot, logits)
        return True

    def _pool_tick(self, fuse_decode: bool) -> bool:
        """Pooled chunked admission (``prefill_pool=K``): top the pool up
        from the queue, then run every policy-admitted due chunk — and, when
        ``fuse_decode`` is set and slots are occupied, the decode step — as
        ONE jitted program against the shared cache.

        The pool tops up in the policy's queue order and the policy's
        ``select`` picks which due chunks run (the stock policies admit a
        FIFO prefix; deadline policies reorder) — either way emitted token
        streams are identical to serialized admission, since each request's
        tokens depend only on its own slot rows; only the schedule changes.
        Bookkeeping order matters: ``_postdecode`` replaces ``self.tokens``
        wholesale with this step's argmax, so it must land BEFORE
        ``_finish_admission`` writes a freshly admitted slot's first token.
        """
        tel = self._telemetry
        with _NULL_SPAN if tel is None else tel.span("admit"):
            while len(self._pending) < self._pool_size and self.queue:
                slot = self._free_slot()
                if slot is None:
                    break
                self._start_pending(slot)
            chunks = [min(self.prefill_chunk, p[2].shape[1] - p[3])
                      for p in self._pending]
            specs = [self._spec(p[0], c)
                     for p, c in zip(self._pending, chunks)]
            picked = self._check_selection(
                self.admission.select(self.num_active, specs), len(specs))
            decode = fuse_decode and self.num_active > 0
            if not picked and not decode:
                return False
            sel = [self._pending[i] for i in picked]
            sel_chunks = [chunks[i] for i in picked]
            toks = tuple({"tokens": jnp.asarray(p[2][:, p[3]:p[3] + c])}
                         for p, c in zip(sel, sel_chunks))
            slot_ids = tuple(jnp.int32(p[1]) for p in sel)
            firsts = tuple(p[3] == 0 for p in sel)
            mask = np.array([r is not None for r in self.slots], bool)
        with (_NULL_SPAN if tel is None else tel.span(
                "pool_step", chunks=len(sel),
                **(self._decode_attrs() if decode else {}))):
            chunk_out, dec_out, self.cache = self._pool_step(
                firsts, bool(decode), self.params, toks, self.cache,
                slot_ids, self.tokens, jnp.asarray(mask))
        if decode:
            dlogits, dstats = dec_out
            if self.monitor is not None:
                self._observe_decode_routing(dstats, mask)
            self.decode_steps += 1
            self._postdecode(dlogits)
        finished = []
        for p, c, (logits, pstats) in zip(sel, sel_chunks, chunk_out):
            r, slot, tk, done = p
            if self.monitor is not None:
                self._observe_prefill(
                    pstats, pad=(tk.shape[1] - len(r.prompt)) - done)
            p[3] = done + c
            if p[3] >= tk.shape[1]:
                finished.append((p, logits))
        for p, logits in finished:
            self._pending.remove(p)
            self._finish_admission(p[0], p[1], logits)
        return True

    def _observe_decode_routing(self, stats, mask) -> None:
        """Fold decode routing counts into the monitor and — when a
        telemetry hub is attached — the per-layer load gauges."""
        self.monitor.observe(stats, mask)
        tel = self._telemetry
        if tel is None or not tel.enabled:
            return
        arr = np.asarray(stats, np.float64)          # (L, B, E)
        if mask is not None:
            arr = arr * np.asarray(mask, np.float64)[None, :, None]
        totals = arr.sum(axis=1)                     # (L, E)
        moe = self.model.cfg.moe
        cf = moe.capacity_factor if moe is not None else None
        for l, row in enumerate(totals):
            tot = float(row.sum())
            if tot <= 0:
                continue
            tel.gauge("moe_expert_load_imbalance",
                      float(row.max()) * row.size / tot,
                      help="max/mean expert load this decode step "
                           "(1.0 = perfectly balanced)", layer=l)
            if cf:
                cap = cf * tot / row.size
                tel.gauge("moe_expert_drop_rate",
                          float(np.maximum(row - cap, 0.0).sum()) / tot,
                          help="estimated fraction of routed tokens over "
                               "per-expert capacity (capacity_factor rule "
                               "applied to this step's counts)", layer=l)

    def _observe_prefill(self, stats, pad: int) -> None:
        """Fold prefill routing counts into the monitor, dropping the first
        ``pad`` positions (left-padding routes token id 0 every time and
        would skew the popularity estimate toward phantom traffic)."""
        arr = np.asarray(stats)                      # (L, 1, S, E)
        real = arr[:, :, max(pad, 0):, :]
        if real.shape[2]:
            self.monitor.observe(real.sum(axis=2))

    def _postdecode(self, logits) -> None:
        """Emit one token per occupied slot; evict finished requests."""
        tel = self._telemetry
        with _NULL_SPAN if tel is None else tel.span("sample"):
            nxt = jnp.argmax(logits[:, :, : self.model.cfg.vocab],
                             axis=-1).astype(jnp.int32)
            self.tokens = nxt
        with _NULL_SPAN if tel is None else tel.span("readback"):
            host = np.asarray(nxt)
        emitted = 0
        with (_NULL_SPAN if tel is None
              else tel.span("emit", emitted=self.num_active)):
            for i, r in enumerate(self.slots):
                if r is None:
                    continue
                r.out_tokens.append(int(host[i, 0]))
                emitted += 1
                if len(r.out_tokens) >= r.max_new_tokens:
                    self.slots[i] = None                 # slot free for reuse
        if tel is not None and tel.enabled and emitted:
            tel.count("serving_tokens_total", emitted,
                      help="tokens emitted", tenant=self._tenant_label)

    def _decode_all(self):
        """One fixed-shape decode over every slot (stats-aware).

        Vacant rows are masked out of cache updates (``row_mask``): their
        state and fill level freeze, which keeps a partially chunk-prefilled
        slot's row byte-stable between chunks. Occupied rows are unaffected
        — attention is batch-row independent — so masking never changes
        emitted tokens."""
        mask = np.array([r is not None for r in self.slots], bool)
        tel = self._telemetry
        with (_NULL_SPAN if tel is None
              else tel.span("decode_step", **self._decode_attrs())):
            out = self._decode(self.params, self.tokens, self.cache,
                               jnp.asarray(mask))
        if self.monitor is not None:
            logits, self.cache, stats = out
            self._observe_decode_routing(stats, mask)
        else:
            logits, self.cache = out
        return logits

    def _decode_attrs(self) -> dict:
        """``decode_step`` span attributes: the slots decoded and their
        cache lengths after this step (padded prompt plus the tokens fed
        back), summed."""
        live = [r for r in self.slots if r is not None]
        return {"active": len(live),
                "valid": sum(self._bucket(len(r.prompt)) + len(r.out_tokens)
                             for r in live)}

    def step(self) -> bool:
        """Admit (whole prefills, or policy-admitted chunks), then decode
        all slots once. Returns False when idle.

        With a prefill pool (``prefill_pool > 1``) the whole step — every
        due prefill chunk AND the decode — is one fused program: a finishing
        request's first decode shifts one engine step later than in the
        serialized schedule, but per-request token streams are unchanged
        (its first token comes from the prefill logits either way)."""
        tel = self._telemetry
        if tel is None or not tel.enabled:
            return self._step_impl()
        with tel.span("engine_step", step=self.decode_steps,
                      tenant=self._tenant_label or None):
            tel.gauge("serving_queue_depth", len(self.queue),
                      help="requests waiting for admission",
                      tenant=self._tenant_label)
            return self._step_impl()

    def _step_impl(self) -> bool:
        if self._pool_size > 1:
            return self._pool_tick(fuse_decode=True)
        worked = self._admit_tick()
        if self.num_active == 0:
            return worked
        logits = self._decode_all()
        self.decode_steps += 1
        self._postdecode(logits)
        return True

    # -- fault tolerance ---------------------------------------------------
    def checkpoint(self) -> dict:
        """Host-side snapshot of the serving state — cache, token buffer,
        slot map, queue, in-flight prefills, emitted-token lengths — for
        step-level rollback after a detected-corrupt step (NaN weights
        caught by the ``HealthMonitor`` mid-step). Request objects are
        shared with the live engine; ``restore`` rewinds their
        ``out_tokens`` to the recorded lengths."""
        reqs = {id(r): r for r in self.slots if r is not None}
        for r in self.queue:
            reqs[id(r)] = r
        for p in self._pending:
            reqs[id(p[0])] = p[0]
        return {
            "cache": jax.tree_util.tree_map(np.asarray, self.cache),
            "tokens": np.asarray(self.tokens),
            "slots": list(self.slots),
            "queue": list(self.queue),
            "pending": [[p[0], p[1], p[2].copy(), p[3]]
                        for p in self._pending],
            "out_lens": [(r, len(r.out_tokens)) for r in reqs.values()],
            "decode_steps": self.decode_steps,
        }

    def restore(self, snap: dict) -> None:
        """Roll the engine back to a ``checkpoint`` snapshot. The recovery
        loop restores, repairs the weights (``repair_moe_params`` from a
        healthy replica), and re-runs the step — deterministic greedy
        decoding makes the re-run byte-identical to a never-faulted run."""
        self.cache = jax.tree_util.tree_map(jnp.asarray, snap["cache"])
        self.tokens = jnp.asarray(snap["tokens"])
        self.slots = list(snap["slots"])
        self.queue = collections.deque(snap["queue"])
        self._pending = [[p[0], p[1], p[2].copy(), p[3]]
                         for p in snap["pending"]]
        for r, ln in snap["out_lens"]:
            del r.out_tokens[ln:]
        self.decode_steps = snap["decode_steps"]

    def requeue(self, slots) -> list[Request]:
        """Fail-stop eviction: push the requests occupying ``slots`` (and
        any in-flight prefill reserving them) back onto the FRONT of the
        queue with their generation reset. The slots' cache rows are
        treated as lost — re-admission re-prefills from the prompt, and
        deterministic greedy decoding re-emits the exact same stream, so a
        re-queued request that completes is byte-identical to its un-failed
        run. Returns the evicted requests (re-queue order)."""
        lost = sorted({int(s) for s in slots})
        for s in lost:
            if not 0 <= s < self.batch_slots:
                raise FaultError(
                    f"cannot requeue slot {s}: out of "
                    f"range({self.batch_slots})")
        lost_set = set(lost)
        victims: list[Request] = []
        for p in list(self._pending):
            if p[1] in lost_set:
                self._pending.remove(p)
                victims.append(p[0])
        for s in lost:
            r = self.slots[s]
            if r is not None:
                self.slots[s] = None
                victims.append(r)
        for r in victims:
            r.out_tokens.clear()
        for r in reversed(victims):
            self.queue.appendleft(r)
        return victims

    # -- driver ------------------------------------------------------------
    def serve(self, reqs: list[Request]) -> list[Request]:
        """Run a request stream to completion, honoring ``arrival`` times
        (measured in engine steps; requests arriving at the same step are
        admitted in list order)."""
        serve_stream(self.step, [(self, reqs)])
        return reqs
