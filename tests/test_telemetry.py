"""Unified serving telemetry: ring buffers, the event bus, span nesting,
the disabled fast path, exports, and the engines' watch-only invariant
(telemetry never changes emitted tokens)."""

import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.serving import (ContinuousEngine, EdfAdmission, EngineConfig,
                           EventBus, HealthMonitor, Request, RingBuffer,
                           Telemetry)
from repro.serving.telemetry import _NULL_SPAN, record_adoption

from _propcheck import given, settings, st  # hypothesis if installed


def _model(arch="qwen3-32b"):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests():
    return [Request(prompt=[1, 2, 3, 4], max_new_tokens=6),
            Request(prompt=[5, 6, 7, 8], max_new_tokens=3),
            Request(prompt=[9, 10, 11, 12], max_new_tokens=6),
            Request(prompt=[2, 4, 6, 8], max_new_tokens=5)]


# -- ring buffer -------------------------------------------------------------

def test_ring_drop_oldest_and_count():
    dropped = []
    ring = RingBuffer(3, on_drop=dropped.append)
    for i in range(5):
        ring.append(i)
    assert list(ring) == [2, 3, 4]
    assert len(ring) == 3
    assert ring.dropped == 2
    assert dropped == [0, 1]
    assert ring[0] == 2 and ring[-1] == 4
    assert ring[1:] == [3, 4]


def test_ring_list_compat():
    ring = RingBuffer(8)
    assert not ring and len(ring) == 0
    ring.extend([1, 2, 3])
    assert ring and list(ring) == [1, 2, 3]
    assert ring[:2] == [1, 2]
    ring.clear()
    assert list(ring) == [] and ring.dropped == 0


def test_ring_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        RingBuffer(0)


@settings(max_examples=25)
@given(st.integers(1, 8), st.integers(0, 40))
def test_ring_retention_property(capacity, n):
    """len == min(n, cap); dropped == max(0, n - cap); contents are the
    LAST cap items in append order."""
    ring = RingBuffer(capacity)
    for i in range(n):
        ring.append(i)
    assert len(ring) == min(n, capacity)
    assert ring.dropped == max(0, n - capacity)
    assert list(ring) == list(range(n))[-capacity:]


# -- event bus ---------------------------------------------------------------

def test_bus_seq_monotonic_and_counts():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    bus = EventBus(capacity=4, clock=clock)
    for i in range(6):
        bus.publish("replan" if i % 2 else "shed", {"i": i}, step=i)
    seqs = [e.seq for e in bus]
    assert seqs == sorted(seqs)
    assert bus.counts["shed"] == 3 and bus.counts["replan"] == 3
    assert len(bus) == 4 and bus.dropped == 2
    assert [e.payload["i"] for e in bus] == [2, 3, 4, 5]
    assert list(bus.events(kind="replan")) == [e for e in bus
                                               if e.kind == "replan"]


def test_bus_deterministic_under_fixed_seed():
    """Same seeded publish sequence -> identical (seq, kind, step) stream."""

    def run(seed):
        rng = np.random.default_rng(seed)
        t = [0.0]

        def clock():
            t[0] += float(rng.random())
            return t[0]

        bus = EventBus(capacity=64, clock=clock)
        kinds = ("shed", "replan", "fault")
        for i in range(20):
            bus.publish(kinds[int(rng.integers(3))], i, step=i)
        return [(e.seq, e.kind, e.step, e.ts) for e in bus]

    assert run(7) == run(7)
    assert run(7) != run(8)


# -- spans -------------------------------------------------------------------

def test_span_nesting_depths():
    tel = Telemetry()
    with tel.span("outer"):
        with tel.span("mid"):
            with tel.span("inner"):
                pass
    by_name = {s.name: s for s in tel.spans}
    assert by_name["outer"].depth == 0
    assert by_name["mid"].depth == 1
    assert by_name["inner"].depth == 2
    # children close first, so finish seq is inner < mid < outer
    assert (by_name["inner"].seq < by_name["mid"].seq
            < by_name["outer"].seq)
    # windows nest: child inside parent
    o, i = by_name["outer"], by_name["inner"]
    assert o.ts <= i.ts and i.ts + i.dur <= o.ts + o.dur + 1e-9


def test_span_closes_on_exception_and_truncates_stack():
    tel = Telemetry()
    with pytest.raises(RuntimeError):
        with tel.span("outer"):
            with tel.span("inner"):
                raise RuntimeError("boom")
    assert tel._stack == []            # nothing leaked on the live stack
    by_name = {s.name: s for s in tel.spans}
    assert by_name["inner"].error == "RuntimeError"
    assert by_name["outer"].error == "RuntimeError"
    # a new top-level span starts back at depth 0
    with tel.span("after"):
        pass
    assert [s for s in tel.spans if s.name == "after"][0].depth == 0


def test_disabled_span_is_shared_singleton():
    tel = Telemetry(enabled=False)
    s1, s2 = tel.span("a", x=1), tel.span("b")
    assert s1 is s2 is _NULL_SPAN      # no per-call allocation
    with s1:
        pass
    tel.count("c_total")
    tel.gauge("g", 1.0)
    tel.observe("h", 0.5)
    assert tel.publish("k", {"v": 1}) is None
    assert len(tel.spans) == 0 and len(tel.bus) == 0
    assert "c_total" not in tel.metrics
    assert "g" not in tel.metrics and "h" not in tel.metrics
    record_adoption(tel, "rounds", step=1)
    record_adoption(None, "rounds", step=1)       # no-op, must not raise
    assert "serving_adoptions_total" not in tel.metrics


# -- metrics -----------------------------------------------------------------

def test_metrics_registry_and_prometheus_text():
    tel = Telemetry()
    tel.count("serving_tokens_total", 3, help="tokens", tenant="a")
    tel.count("serving_tokens_total", 2, tenant="b")
    tel.gauge("serving_queue_depth", 5, tenant="a")
    tel.observe("serving_ttft_steps", 3.0, bounds=(1.0, 4.0), tenant="a")
    tel.observe("serving_ttft_steps", 9.0, bounds=(1.0, 4.0), tenant="a")
    text = tel.prometheus_text()
    assert '# TYPE serving_tokens_total counter' in text
    assert 'serving_tokens_total{tenant="a"} 3' in text
    assert 'serving_tokens_total{tenant="b"} 2' in text
    assert 'serving_queue_depth{tenant="a"} 5' in text
    # histogram buckets are cumulative with an implicit +Inf
    assert 'serving_ttft_steps_bucket{tenant="a",le="4"} 1' in text
    assert 'serving_ttft_steps_bucket{tenant="a",le="+Inf"} 2' in text
    assert 'serving_ttft_steps_count{tenant="a"} 2' in text
    snap = tel.snapshot()
    assert snap["metrics"]["serving_tokens_total"]["kind"] == "counter"
    json.loads(json.dumps(snap))       # snapshot must be JSON-clean

    with pytest.raises(TypeError):
        tel.metrics.gauge("serving_tokens_total")   # kind mismatch


# -- exports -----------------------------------------------------------------

def test_jsonl_and_chrome_trace_round_trip():
    tel = Telemetry()
    with tel.span("engine_step", step=0):
        with tel.span("decode_step", tenant="a"):
            pass
        with tel.span("readback"):
            pass
    tel.publish("shed", {"reason": "deadline:late"}, step=0)
    for line in tel.jsonl().splitlines():
        json.loads(line)               # every JSONL line round-trips
    trace = json.loads(json.dumps(tel.chrome_trace()))
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert phases <= {"X", "i", "M"} and "X" in phases and "i" in phases
    ts = [e["ts"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)            # timeline order
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"engine_step", "decode_step", "readback", "shed"} <= names
    # tenant maps to its own track with a thread_name record
    tids = {e["tid"] for e in trace["traceEvents"]
            if e["ph"] == "X" and e["args"].get("tenant") == "a"}
    assert tids == {1}
    thread_names = [e["args"]["name"] for e in trace["traceEvents"]
                    if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "tenant:a" in thread_names


def test_records_sorted_and_payloads_sanitized():
    tel = Telemetry()
    with tel.span("s"):
        pass
    tel.publish("fault", {"arr": np.arange(3), "bad": float("nan")})
    recs = tel.records()
    ts = [r["ts"] for r in recs]
    assert ts == sorted(ts)
    ev = [r for r in recs if r["type"] == "event"][0]
    assert ev["payload"]["arr"] == [0, 1, 2]
    assert ev["payload"]["bad"] == "nan"
    json.loads(tel.jsonl().splitlines()[-1])


# -- engine integration ------------------------------------------------------

def test_engine_tokens_identical_with_telemetry():
    """Telemetry only watches: same stream, telemetry on vs None, byte-
    identical tokens — and the hub actually recorded the serve."""
    cfg, model, params = _model()
    base = ContinuousEngine(model, params, 4, 48,
                            config=EngineConfig(prefill_len=4))
    ref = _requests()
    base.serve(ref)

    tel = Telemetry()
    traced = ContinuousEngine(model, params, 4, 48,
                              config=EngineConfig(prefill_len=4,
                                                  telemetry=tel))
    live = _requests()
    traced.serve(live)
    assert [r.out_tokens for r in live] == [r.out_tokens for r in ref]

    names = {s.name for s in tel.spans}
    assert {"engine_step", "prefill", "decode_step"} <= names
    tokens = sum(len(r.out_tokens) for r in live)
    assert tel.metrics["serving_tokens_total"].value(tenant="") == tokens
    assert "serving_queue_depth" in tel.metrics
    assert "serving_ttft_steps" in tel.metrics
    # telemetry=None engines carry no hub at all (pre-telemetry path)
    assert base._telemetry is None


def test_engine_disabled_hub_records_nothing():
    cfg, model, params = _model()
    tel = Telemetry(enabled=False)
    eng = ContinuousEngine(model, params, 2, 32,
                           config=EngineConfig(prefill_len=4,
                                               telemetry=tel))
    eng.serve(_requests()[:2])
    assert len(tel.spans) == 0 and len(tel.bus) == 0
    assert "serving_tokens_total" not in tel.metrics


def test_shed_events_ring_bounded():
    """An overload burst under shed-mode EDF with a tiny event_capacity:
    the per-engine shed list keeps only the newest events and counts the
    evictions (and every shed still lands on the hub's bus)."""
    cfg, model, params = _model()
    tel = Telemetry()
    eng = ContinuousEngine(
        model, params, 2, 32,
        config=EngineConfig(
            admission=EdfAdmission(chunk=4, budget=6, shed=True,
                                   queue_cap=2),
            prefill_len=4, telemetry=tel, event_capacity=2))
    reqs = [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=3,
                    arrival=0.0, deadline=0.5) for i in range(8)]
    sheds = 0
    for r in reqs:
        if eng.submit(r) is not None:
            sheds += 1
    while eng.step():
        pass
    assert sheds >= 3, "burst did not overload — test setup broken"
    assert len(eng.shed_events) == 2
    assert eng.shed_events.dropped == sheds - 2
    assert tel.metrics["serving_events_total"].value(kind="shed") == sheds
    assert len([e for e in tel.bus if e.kind == "shed"]) == sheds


# -- health monitor ----------------------------------------------------------

def test_health_ewma_cold_start_warmup():
    """The first min_observations samples average with EQUAL weight, so a
    slow first step (compile) cannot bias the straggler baseline; the
    detector arms only after warm-up."""
    h = HealthMonitor(n_devices=2, min_observations=4, halflife=8.0,
                      straggler_ratio=3.0)
    assert not h.armed(0) and h.warming_devices == (0, 1)
    samples = [0.3, 0.1, 0.1, 0.1]     # slow cold start, then steady
    for dt in samples:
        h.observe_step_time(0, dt)
        h.observe_step_time(1, 0.1)
    assert h.armed(0) and h.warming_devices == ()
    # warm-up is a plain mean — NOT decay-weighted toward the 1.0 sample
    np.testing.assert_allclose(h.step_times()[0], np.mean(samples))
    # device 0's cold start must not read as a straggler vs device 1
    h.heartbeat(0, 4)
    h.heartbeat(1, 4)
    assert [e for e in h.check(4) if e.kind == "straggler"] == []


def test_health_not_flagged_while_warming():
    h = HealthMonitor(n_devices=2, min_observations=4, straggler_ratio=2.0)
    for _ in range(3):
        h.observe_step_time(0, 10.0)   # looks straggling, but still warming
        h.observe_step_time(1, 0.1)
    h.heartbeat(0, 3)
    h.heartbeat(1, 3)
    assert h.check(3) == []
    h.observe_step_time(0, 10.0)       # 4th sample arms the detector
    h.observe_step_time(1, 0.1)
    assert any(e.kind == "straggler" and e.device == 0 for e in h.check(4))


def test_health_events_ring_bounded_and_published():
    tel = Telemetry()
    h = HealthMonitor(n_devices=1, capacity=2, telemetry=tel)
    for step in range(3):
        assert not h.observe_output({"x": np.array([np.nan])}, step)
    assert len(h.events) == 2 and h.events.dropped == 1
    assert len(h.drain()) == 2         # pending ring is bounded too
    assert h.drain() == []
    assert tel.metrics["serving_faults_total"].value(kind="nan") == 3
    assert len([e for e in tel.bus if e.kind == "fault"]) == 3


def test_health_gauges_exported():
    tel = Telemetry()
    h = HealthMonitor(n_devices=1, min_observations=2, telemetry=tel)
    h.observe_step_time(0, 0.2)
    assert tel.metrics["device_detector_armed"].value(device="0") == 0.0
    h.observe_step_time(0, 0.2)
    assert tel.metrics["device_detector_armed"].value(device="0") == 1.0
    np.testing.assert_allclose(
        tel.metrics["device_step_seconds"].value(device="0"), 0.2)


# -- config ------------------------------------------------------------------

def test_event_capacity_validated():
    with pytest.raises(ValueError):
        EngineConfig(event_capacity=0)


# -- engine spans ------------------------------------------------------------

def _chunked_requests():
    return [Request(prompt=list(range(1, 20)), max_new_tokens=5),
            Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=4),
            Request(prompt=list(range(30, 41)), max_new_tokens=3)]


def _serve_chunked(telemetry):
    cfg, model, params = _model("phi3.5-moe-42b-a6.6b")
    eng = ContinuousEngine(model, params, 2, 48,
                           config=EngineConfig(prefill_chunk=8, kernels=True,
                                               bucket_policy="step:8",
                                               telemetry=telemetry))
    reqs = _chunked_requests()
    eng.serve(reqs)
    return [r.out_tokens for r in reqs]


def test_spans_change_no_token_and_cost_nothing_when_absent(monkeypatch):
    """Chunked prefill on the kernel path: byte-identical tokens with the
    hub attached (annotating the profiler), disabled, or absent; with no
    hub no ``TraceAnnotation`` is entered, with one each span enters one."""
    entered = []

    class Counting:
        def __init__(self, name, **attrs):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    base = _serve_chunked(None)
    assert entered == []
    assert _serve_chunked(Telemetry(enabled=False)) == base
    assert entered == []
    tel = Telemetry(jax_profiler=True)
    assert _serve_chunked(tel) == base
    assert entered == [s.name for s in sorted(tel.spans, key=lambda s: s.ts)]
    assert {"engine_step", "admit", "prefill_chunk", "first_token",
            "decode_step", "sample", "readback", "emit"} <= set(entered)


def test_span_tree_and_request_ids():
    """Every child of an ``engine_step`` sits one level below it; every
    span of one request's chunks and first token carries its ``rid``."""
    tel = Telemetry()
    cfg, model, params = _model("phi3.5-moe-42b-a6.6b")
    eng = ContinuousEngine(model, params, 2, 48,
                           config=EngineConfig(prefill_chunk=8,
                                               bucket_policy="step:8",
                                               telemetry=tel))
    reqs = _chunked_requests()
    eng.serve(reqs)
    recs = sorted(tel.spans, key=lambda s: s.ts)
    assert {s.depth for s in recs if s.name == "engine_step"} == {0}
    assert {s.depth for s in recs if s.name != "engine_step"} == {1}
    rids = {r.rid for r in reqs}
    assert len(rids) == len(reqs)
    chunks = [s for s in recs if s.name == "prefill_chunk"]
    # 19 -> 24 (3 chunks), 5 -> 8 (1), 11 -> 16 (2)
    assert [c.attrs["rid"] for c in chunks].count(reqs[0].rid) == 3
    assert {c.attrs["rid"] for c in chunks} == rids
    assert sum(c.attrs["real"] for c in chunks) == 19 + 5 + 11
    assert [c.attrs["last"] for c in chunks].count(True) == 3
    firsts = [s.attrs["rid"] for s in recs if s.name == "first_token"]
    assert sorted(firsts) == sorted(rids)
    emitted = sum(s.attrs["emitted"] for s in recs if s.name == "emit")
    assert emitted + len(firsts) == sum(len(r.out_tokens) for r in reqs)


def _scopes_in(program) -> set:
    """Scope names in a compiled program's HLO metadata, or in a lowered
    program's locations (where the compiler has not yet folded away the
    ops of a one-device collective)."""
    import re
    if isinstance(program, jax.stages.Compiled):
        paths = re.findall(r'op_name="([^"]*)"', program.as_text())
    else:
        paths = re.findall(r'loc\("([^"]*)"',
                           program.as_text(debug_info=True))
    names = set()
    for path in paths:
        parts = path.split("/")
        names.update(parts)
        names.update("/".join(parts[i:i + 2]) for i in range(len(parts)))
    return names


def test_lowered_programs_carry_model_scopes():
    """The compiled decode and chunk programs name their device work: the
    layer scan's weight slices, attention and its cache write, the MoE
    layer's parts and the output head."""
    cfg, model, params = _model("phi3.5-moe-42b-a6.6b")
    eng = ContinuousEngine(model, params, 2, 32,
                           config=EngineConfig(prefill_chunk=8, kernels=True,
                                               bucket_policy="step:8"))
    want = {"layer_weights", "attn", "attn/cache_write", "moe/router",
            "moe/dispatch", "moe/experts", "moe/combine", "lm_head"}
    mask = jax.numpy.ones((2,), bool)
    dec = eng._decode.lower(eng.params, eng.tokens, eng.cache,
                            mask).compile()
    assert want <= _scopes_in(dec)
    toks = {"tokens": jax.numpy.ones((1, 8), jax.numpy.int32)}
    slot = jax.numpy.int32(0)
    for fn, name in ((eng._chunk_first, "prefill_chunk_first"),
                     (eng._chunk, "prefill_chunk")):
        lowered = fn.lower(eng.params, toks, eng.cache, slot)
        assert f"jit_{name}" in lowered.as_text()
        assert want <= _scopes_in(lowered.compile())


def test_expert_parallel_program_names_its_exchange():
    """On a mesh the MoE layer's collectives run under ``moe/exchange``
    (read from the lowered program: one device's exchange compiles away)."""
    from repro.launch.mesh import make_ep_mesh
    from repro.serving import DistributedEngine

    cfg, model, params = _model("phi3.5-moe-42b-a6.6b")
    mesh = make_ep_mesh(1)
    eng = DistributedEngine(model, params, 2, 32, mesh=mesh, moe_impl="ep",
                            config=EngineConfig(prefill_len=8))
    mask = jax.numpy.ones((2,), bool)
    with jax.set_mesh(mesh):
        dec = jax.jit(eng.model.decode_step).lower(
            eng.params, eng.tokens, eng.cache, mask)
    assert {"moe/router", "moe/dispatch", "moe/exchange", "moe/experts",
            "moe/combine"} <= _scopes_in(dec)
