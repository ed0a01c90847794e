"""The engine's spans and the model's scopes as the benchmark reads them:
the span tree and its counts against the harness's records of the same
steps, the reduction of a trace to span records and scoped ops, and the
readers built on them, on hand-made traces and on slices recorded on a
TPU v5e."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from benchmarks.chip import peaks, run, spans, spantrace, tiny  # noqa: E402
from benchmarks.chip import traffic, xtrace  # noqa: E402
from benchmarks.chip.spans import Span, StatEvent  # noqa: E402

D0 = "/device:TPU:0"
HOST = "/host:CPU"
NEW = ("host_gap_ms.decode", "weight_slice_ms.decode",
       "moe_layer_ms.decode", "cache_write_ms.decode")


def test_engine_spans_match_the_harness_records():
    """A chunked-prefill engine driven by the benchmark's client with the
    hub attached: each step is one ``engine_step`` whose children are the
    layer boundaries in order, and the counts on its ``prefill_chunk`` and
    ``decode_step`` spans equal the harness's record of that step."""
    import jax

    from repro.serving import Request, Telemetry

    cell = tiny.cell()
    cfg = cell["cfg"]
    _, eng = run.build(cfg, 1, 7, jax.devices())
    hub = Telemetry()
    eng.telemetry = hub
    chunk = cfg["serving"]["prefill_chunk"]
    rng = np.random.default_rng(7)
    tracks = [run.Track(Request(prompt=list(rng.integers(1, 500, n)),
                                max_new_tokens=m), 0.0, True,
                        traffic.padded(n, chunk))
              for n, m in ((150, 6), (40, 3), (64, 5), (100, 1))]
    drv = run.Client(eng, tracks, clock=lambda: 0.0)
    drv.submit_due(0.0)
    while not drv.idle():
        drv.step(traced=True)
    records = list(hub.spans)
    assert spantrace.count_mismatch(drv.steps, records) == 0
    tops = [r for r in records if r.name == "engine_step"]
    assert len(tops) == len(drv.steps)
    order = ("admit", "prefill_chunk", "first_token", "decode_step",
             "sample", "readback", "emit")
    for top in tops:
        kids = sorted((r for r in records if r.depth == 1
                       and top.ts <= r.ts <= top.ts + top.dur),
                      key=lambda r: r.ts)
        names = [k.name for k in kids]
        assert names[0] == "admit"
        assert names == sorted(names, key=order.index)
    assert sum(r.attrs["real"] for r in records
               if r.name == "prefill_chunk") == 150 + 40 + 64 + 100


def test_innermost_scope():
    f = spans.innermost_scope
    assert f("jit(decode_step)/while/body/closed_call/moe/experts/"
             "jit(moe_gmm)/pallas_call") == "moe/experts"
    assert f("jit(decode_step)/while/body/closed_call/attn/cache_write/"
             "select_n") == "attn/cache_write"
    assert f("jit(decode_step)/while/body/closed_call/attn/bsd,dhk->bshk/"
             "dot_general") == "attn"
    assert f("jit(decode_step)/while/body/closed_call/layer_weights/"
             "squeeze") == "layer_weights"
    assert f("jit(decode_step)/lm_head/dot_general") == "lm_head"
    assert f("jit(decode_step)/jit(_take)/gather") == spans.UNSCOPED
    assert f("jit(decode_attn)/moe") == spans.UNSCOPED
    assert f("") == spans.UNSCOPED


def _steps(t0, chunk, length=0.100):
    """The host's spans of one engine step at t0 (seconds): admit 1 ms,
    a 4 ms chunk dispatch if ``chunk``, a 2 ms decode dispatch, 1 ms of
    sampling, the read-back until 2 ms before the step's end, emit."""
    out = [Span("engine_step", t0, t0 + length, {"step": 1}),
           Span("admit", t0, t0 + 0.001, {})]
    at = t0 + 0.001
    if chunk:
        out.append(Span("prefill_chunk", at, at + 0.004,
                        {"real": 500, "start": 0, "last": 0}))
        at += 0.004
    out += [Span("decode_step", at, at + 0.002, {"active": 3,
                                                 "valid": 3000}),
            Span("sample", at + 0.002, at + 0.003, {}),
            Span("readback", at + 0.003, t0 + length - 0.002, {}),
            Span("emit", t0 + length - 0.002, t0 + length, {"emitted": 3})]
    return out


def test_steps_and_host_gaps():
    sp = sorted(_steps(0.0, False) + _steps(0.101, True)
                + _steps(0.202, False), key=lambda s: (s.start, -s.end))
    st = spans.steps(sp)
    assert [len(s["children"]) for s in st] == [5, 6, 5]
    # readback ends 98 ms into a step; the next step's first dispatch
    # ends 101 + 5 (chunk) or 101 + 3 ms later.
    assert spans.host_gaps(sp) == pytest.approx([0.008, 0.006])
    # A step that only admits (no slot busy: the engine waited for an
    # arrival) starts no pair.
    idle = [Span("engine_step", 0.400, 0.410, {"step": 3}),
            Span("admit", 0.400, 0.401, {}),
            Span("prefill_chunk", 0.401, 0.405, {"real": 500})]
    assert spans.host_gaps(sorted(sp + idle, key=lambda s: s.start)) == \
        pytest.approx([0.008, 0.006])


def test_idle_by_span():
    sp = sorted(_steps(0.0, False) + _steps(0.101, False),
                key=lambda s: (s.start, -s.end))
    ops = [xtrace.Op("a", "jit_decode_step(1)", 0.001, 0.097),
           xtrace.Op("b", "jit_decode_step(1)", 0.104, 0.198)]
    idle = spans.idle_by_span(xtrace.Device(ops, []), sp)
    # 97-98 readback, 98-100 emit, 100-101 between steps, 101-102 admit,
    # 102-104 decode_step (its dispatch).
    assert idle == pytest.approx({"readback": 0.001, "emit": 0.002,
                                  "between steps": 0.001, "admit": 0.001,
                                  "decode_step": 0.002})


def _scoped_ctx(events, programs, steps=(), window=1.0):
    ctx = run.Context(cfg=json.loads(
        (ROOT / "benchmarks/chip/configs/phi35moe-1chip.json").read_text()),
        mix={}, chips=1, seconds=1.0, peaks=peaks.peaks("TPU v5 lite"),
        steps=list(steps), tracks=[], trace=xtrace.devices(events),
        trace_window_s=window)
    ctx.spans = spans.spans(events)
    ctx.scoped = spans.scoped(events, programs)
    return ctx


def test_op_paths_from_compiled_hlo():
    """The name paths come from the compiled program's HLO metadata."""
    import jax
    import jax.numpy as jnp

    def step(w, x):
        with jax.named_scope("moe/experts"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("lm_head"):
            return y @ w.T

    w = jnp.ones((16, 16))
    text = jax.jit(step).lower(w, jnp.ones((4, 16))).compile().as_text()
    name, paths = spans.op_paths(text)
    assert name == "jit_step"
    scopes = {spans.innermost_scope(p) for p in paths.values()}
    assert {"moe/experts", "lm_head"} <= scopes
    assert any(p.startswith("jit(step)/lm_head/") for p in paths.values())


def test_scope_readers_on_a_hand_made_trace():
    ev, paths = [], {}
    for s in (0.0, 0.030):
        ev.append(StatEvent(D0, "XLA Modules", "jit_decode_step(7)", s,
                            s + 0.010))
        for name, a, b, path in (
                ("ds.1", 0.000, 0.003, "jit(decode_step)/while/body/"
                 "layer_weights/squeeze"),
                ("moe_gmm.6", 0.003, 0.006, "jit(decode_step)/while/body/"
                 "closed_call/moe/experts/pallas_call"),
                ("fusion.2", 0.006, 0.007, "jit(decode_step)/while/body/"
                 "moe/router/div"),
                ("fusion.130", 0.007, 0.009, "jit(decode_step)/while/body/"
                 "attn/cache_write/select_n"),
                ("copy.96", 0.009, 0.010, "")):
            ev.append(StatEvent(D0, "XLA Ops", name, s + a, s + b))
            paths[name] = path
    ev += [StatEvent(HOST, "python", sp.name, sp.start, sp.end, sp.attrs)
           for sp in _steps(-0.002, False, 0.015)
           + _steps(0.027, False, 0.015)]
    ctx = _scoped_ctx(ev, {"jit_decode_step": paths})
    read = run.load_metric
    assert read("weight_slice_ms.decode")(ctx) == pytest.approx(3.0)
    assert read("moe_layer_ms.decode")(ctx) == pytest.approx(4.0)
    assert read("cache_write_ms.decode")(ctx) == pytest.approx(2.0)
    # The read-back ends at 11 ms, the next decode dispatch at 30 ms.
    assert read("host_gap_ms.decode")(ctx) == pytest.approx(19.0)
    secs = spans.scope_seconds(ctx.scoped[D0], ("decode_step",))
    assert secs[spans.UNSCOPED] == pytest.approx(0.002)


def test_new_readers_are_silent_without_their_source():
    ctx = run.Context(cfg={}, mix={}, chips=1, seconds=1.0, peaks=None,
                      steps=[], tracks=[])
    for name in NEW:
        assert run.load_metric(name)(ctx) is None
    ctx.trace = {D0: xtrace.Device([], [])}
    ctx.spans, ctx.scoped = [], {D0: []}
    for name in NEW:
        assert run.load_metric(name)(ctx) is None


def _old_slice():
    d = json.loads((ROOT / "benchmarks/chip/testdata/trace_v5e_chat.json"
                    ).read_text())
    return [xtrace.Event(p, ln, xtrace.op_name(n), s * 1e-6, e * 1e-6)
            for p, ln, n, s, e in d["events"]]


def test_existing_readers_read_as_before_on_the_old_slice():
    """The readers the benchmark has give the numbers they gave before the
    programs were named and scoped, on the slice recorded then: one
    512-token chunk (``jit__unknown``) and two decode steps."""
    ev = _old_slice()
    steps = [run.Step(0.0, 0.1, chunk_real=508, chunk_start=0,
                      chunk_last=False, ran_chunk=True, decode_active=4,
                      decode_valid=4 * 1500, traced=True),
             run.Step(0.1, 0.2, decode_active=4, decode_valid=4 * 1501,
                      traced=True)]
    ctx = run.Context(cfg=json.loads(
        (ROOT / "benchmarks/chip/configs/phi35moe-1chip.json").read_text()),
        mix={}, chips=1, seconds=1.0, peaks=peaks.peaks("TPU v5 lite"),
        steps=steps, tracks=[], trace=xtrace.devices(ev),
        trace_window_s=0.25)
    got = {m: run.load_metric(m)(ctx) for m in (
        "decode_step_ms", "prefill_ms_per_ktok", "device_idle_share",
        "moe_gmm_roofline.decode", "moe_gmm_roofline.prefill",
        "decode_attn_roofline", "step_mfu.decode", "step_mfu.prefill")}
    assert got == pytest.approx(OLD_SLICE_READINGS, rel=1e-9)


# Read with the readers and the slice as they stood before the programs
# were named and scoped.
OLD_SLICE_READINGS = {
    "decode_step_ms": 70.23217450000001,
    "prefill_ms_per_ktok": 191.3196614173228,
    "device_idle_share": 7.014299999999974,
    "moe_gmm_roofline.decode": 71.3901362044902,
    "moe_gmm_roofline.prefill": 22.089161453879605,
    "decode_attn_roofline": 8.92587842693425,
    "step_mfu.decode": 0.056530842396418444,
    "step_mfu.prefill": 4.2744490881177075}


def _spans_slice():
    d = json.loads((ROOT / "benchmarks/chip/testdata/trace_v5e_spans.json"
                    ).read_text())
    ev = [StatEvent(p, ln, n, s * 1e-6, e * 1e-6, st)
          for p, ln, n, s, e, st in d["events"]]
    return ev, d["programs"]


def _by_hand(ev, programs, part: str) -> float:
    """Device time per decode program of the leaf ops whose name path
    holds ``part``: summed op by op over the slice's decode modules."""
    mods = [e for e in ev if e.line == "XLA Modules"
            and e.name.startswith("jit_decode_step")]
    paths = programs["jit_decode_step"]
    total = 0.0
    for m in mods:
        for o in ev:
            if (o.line == "XLA Ops" and m.start <= o.start <= m.end
                    and part in paths.get(o.name, "")
                    and not o.name.startswith("while")):
                total += o.end - o.start
    return 1000.0 * total / len(mods)


def test_new_readers_on_the_recorded_slice():
    """On a slice recorded with the hub attached (the last chunk of a
    prompt, its first token and a decode, then a decode-only step), each
    new reader equals the number summed by hand."""
    ev, programs = _spans_slice()
    modules = {e.name.split("(")[0] for e in ev if e.line == "XLA Modules"}
    assert "jit__unknown" not in modules
    assert {"jit_prefill_chunk", "jit_decode_step"} <= modules
    ctx = _scoped_ctx(ev, programs)
    read = run.load_metric
    for name, part, approx in (
            ("weight_slice_ms.decode", "/layer_weights/", 30.87),
            ("moe_layer_ms.decode", "/moe/", 14.01),
            ("cache_write_ms.decode", "/attn/cache_write/", 4.88)):
        got = read(name)(ctx)
        assert got == pytest.approx(_by_hand(ev, programs, part), rel=1e-9)
        assert got == pytest.approx(approx, abs=0.01)
    # The chunk step's read-back ends, then the next step's decode
    # dispatch ends this much later (host clock).
    back = [e for e in ev if e.name == "readback"][0]
    nxt = [e for e in ev if e.name == "decode_step"][1]
    assert read("host_gap_ms.decode")(ctx) == pytest.approx(
        1000.0 * (nxt.end - back.end), rel=1e-9)
    chunk = [e.stats for e in ev if e.name == "prefill_chunk"]
    # 1020 prompt tokens padded to 1024: the second chunk holds the last
    # 512 real ones, from position 508.
    assert chunk == [{"rid": 28, "slot": 6, "real": 512, "start": 508,
                      "last": 1}]
    # What no scope reaches: the two copies of the whole stacked cache the
    # compiler puts after the layer loop (no metadata), 7% of decode.
    secs = spans.scope_seconds(ctx.scoped[D0], ("decode_step",))
    share = secs[spans.UNSCOPED] / sum(secs.values())
    assert share == pytest.approx(0.0703, abs=0.001)
    unscoped = {o.name for o, s in ctx.scoped[D0] if s == spans.UNSCOPED
                and o.leaf and "decode_step" in o.module
                and o.end - o.start > 1e-3}
    assert unscoped == {"copy.96", "copy.97"}
    assert programs["jit_decode_step"]["copy.96"] == ""
