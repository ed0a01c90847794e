"""A traced run of one cell with the engine's telemetry hub attached, and
what its spans and scopes show. Not part of a benchmark run.

    python3 benchmarks/chip/spantrace.py --workload W --seed N \
        --seconds S [--dump PATH.json.gz]

It runs the cell as ``run.py --trace 1`` does, with a
``Telemetry(jax_profiler=True)`` hub attached to the engine for the traced
part of the window, the trace loaded with the spans' stats
(``spans.load``), and the name paths of the decode and chunk programs'
instructions read from their compiled HLO in set-up (``program_paths``).
It prints one JSON line: the cell's per-layer metrics, the readers of
spans and scopes (``host_gap_ms.decode``, ``weight_slice_ms.decode``,
``moe_layer_ms.decode``, ``cache_write_ms.decode``), the decode and chunk
programs' device time by scope, the device's idle time by engine span,
the largest difference between the engine spans' counts and the harness's
step records, and the host time one span costs. ``--dump`` writes the
traced device ops and modules, the engine spans and the programs' name
paths, for reading offline.

With ``--profile 0`` the run is an untraced one (``run.py --trace 0``)
with a hub that records spans in memory only, attached from set-up on;
the line then gives the window's median span durations and host gaps
(host clock), beside the end-to-end metrics.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip import reading, run, spans, xtrace  # noqa: E402

READERS = ("host_gap_ms.decode", "weight_slice_ms.decode",
           "moe_layer_ms.decode", "cache_write_ms.decode")


def program_paths(eng, chunk: int) -> dict[str, dict[str, str]]:
    """``spans.op_paths`` of the engine's decode and chunk programs,
    compiled for the shapes the cell runs (the compile the engine's own
    calls then find in the cache)."""
    import jax.numpy as jnp

    toks = {"tokens": jnp.zeros((1, chunk), jnp.int32)}
    slot = jnp.int32(0)
    mask = jnp.ones((eng.batch_slots,), bool)
    lowered = [eng._decode.lower(eng.params, eng.tokens, eng.cache, mask),
               eng._chunk_first.lower(eng.params, toks, eng.cache, slot),
               eng._chunk.lower(eng.params, toks, eng.cache, slot)]
    return dict(spans.op_paths(low.compile().as_text()) for low in lowered)


def traced_run(cell, seed: int, seconds: float, *, require_tpu=True,
               check_mode="compare"):
    """``run.run_cell`` with ``trace`` on, the hub attached to the engine
    from the profiler's start to its stop, and the spans' stats kept.
    Returns the result (with ``ctx``, ``events`` and ``programs``, the
    programs' name paths) and the hub."""
    import jax

    from repro.serving import Telemetry

    hub = Telemetry(capacity=1 << 16, jax_profiler=True)
    built = {}
    build, start, stop, load = (run.build, jax.profiler.start_trace,
                                jax.profiler.stop_trace, xtrace.load)

    def build_keep(cfg, *a, **kw):
        params, eng = build(cfg, *a, **kw)
        built["eng"] = eng
        built["programs"] = program_paths(eng,
                                          cfg["serving"]["prefill_chunk"])
        return params, eng

    def start_with_hub(log_dir, *a, **kw):
        start(log_dir, *a, **kw)
        built["eng"].telemetry = hub

    def stop_with_hub(*a, **kw):
        built["eng"].telemetry = None
        stop(*a, **kw)

    run.build = build_keep
    jax.profiler.start_trace = start_with_hub
    jax.profiler.stop_trace = stop_with_hub
    xtrace.load = spans.load
    try:
        res = run.run_cell(cell, seed, seconds, True, keep=True,
                           require_tpu=require_tpu, check_mode=check_mode)
    finally:
        run.build = build
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        xtrace.load = load
    ctx = res["ctx"]
    res["programs"] = built["programs"]
    ctx.spans = spans.spans(res["events"])
    ctx.scoped = spans.scoped(res["events"], res["programs"])
    return res, hub


def count_mismatch(steps, records) -> int:
    """The largest difference between the counts on the engine's spans
    (``records``: the hub's ``SpanRecord``s) and the harness's records of
    the same steps (``run.Step``, those traced); -1 where the two list
    different numbers of steps."""
    tops = sorted((r for r in records if r.name == "engine_step"),
                  key=lambda r: r.ts)
    traced = [s for s in steps if s.traced]
    if len(tops) != len(traced):
        return -1
    worst = 0
    for top, st in zip(tops, traced):
        kids = [r for r in records if r.depth == top.depth + 1
                and top.ts <= r.ts <= top.ts + top.dur]
        chunk = [r.attrs for r in kids if r.name == "prefill_chunk"]
        dec = [r.attrs for r in kids if r.name == "decode_step"]
        eng = (sum(c["real"] for c in chunk),
               chunk[-1]["start"] if chunk else 0,
               int(chunk[-1]["last"]) if chunk else 0, int(bool(chunk)),
               sum(d["active"] for d in dec), sum(d["valid"] for d in dec))
        har = (st.chunk_real, st.chunk_start, int(st.chunk_last),
               int(st.ran_chunk), st.decode_active, st.decode_valid)
        worst = max(worst, max(abs(a - b) for a, b in zip(eng, har)))
    return worst


def span_cost_us(n: int = 20000) -> dict:
    """Host microseconds of one span with three attributes, with the
    profiler tracing (annotation on) and with the hub on its own."""
    import jax

    from repro.serving import Telemetry

    out = {}
    for label, prof in (("annotated", True), ("plain", False)):
        hub = Telemetry(capacity=1024, jax_profiler=prof)
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            t = time.perf_counter()
            for i in range(n):
                with hub.span("probe", rid=i, slot=3, real=512):
                    pass
            out[label] = 1e6 * (time.perf_counter() - t) / n
            jax.profiler.stop_trace()
    return out


def summary(res, hub) -> dict:
    ctx, ev = res["ctx"], res["events"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    for name in READERS:
        metrics[name] = run.load_metric(name)(ctx)
    by_scope, idle = {}, {}
    for plane, ops in ctx.scoped.items():
        for label, programs in (("decode", reading.DECODE),
                                ("prefill", reading.PREFILL)):
            secs = spans.scope_seconds(ops, programs)
            total = sum(secs.values())
            by_scope[f"{plane} {label}"] = {
                "leaf_s": total, "share": {
                    k: v / total for k, v in sorted(secs.items())}}
        idle[plane] = spans.idle_by_span(ctx.trace[plane], ctx.spans)
    gaps = spans.host_gaps(ctx.spans)
    records = list(hub.spans)
    per_step = (len(records) / sum(r.name == "engine_step" for r in records)
                if records else 0.0)
    traced = [s for s in ctx.steps if s.traced]
    modules = sorted({e.name.split("(")[0] for e in ev
                      if e.line == xtrace.MODULES_LINE})
    return {"metrics": metrics, "trace": res["trace"],
            "correct": res["correct"], "check": res["check"],
            "traced_steps": len(traced), "host_gaps": len(gaps),
            "count_mismatch": count_mismatch(ctx.steps, records),
            "spans_in_trace": len(ctx.spans), "spans_recorded": len(records),
            "spans_per_step": per_step, "by_scope": by_scope,
            "idle_by_span": idle, "modules": modules,
            "breakdown": res.get("breakdown")}


def host_run(cell, seed: int, seconds: float):
    """``run.run_cell`` with ``trace`` off and a hub without the profiler
    attached from set-up on. Returns the result (with ``ctx``) and the
    hub."""
    from repro.serving import Telemetry

    hub = Telemetry(capacity=1 << 17)
    build = run.build

    def build_hub(*a, **kw):
        params, eng = build(*a, **kw)
        eng.telemetry = hub
        return params, eng

    run.build = build_hub
    try:
        res = run.run_cell(cell, seed, seconds, False, keep=True)
    finally:
        run.build = build
    return res, hub


def host_summary(res, hub) -> dict:
    """The window's engine steps as the hub recorded them: the median
    duration of each span and the host gaps (``spans.host_gaps``)."""
    ctx = res["ctx"]
    recs = sorted(hub.spans, key=lambda r: r.ts)
    tops = [r for r in recs if r.name == "engine_step"]
    # The client's steps are the last ones (warm-up stepped the engine
    # directly); keep those inside the window.
    tops = tops[len(tops) - len(ctx.steps):]
    keep = [(t.ts, t.ts + t.dur) for t, st in zip(tops, ctx.steps)
            if 0.0 <= st.t0 and st.t1 <= ctx.seconds]
    lo, hi = keep[0][0], keep[-1][1]
    sp = [spans.Span(r.name, r.ts, r.ts + r.dur, r.attrs) for r in recs
          if lo <= r.ts <= hi]
    dur = {}
    for x in sp:
        dur.setdefault(x.name, []).append(x.end - x.start)
    gaps = sorted(spans.host_gaps(sp))
    return {"metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "correct": res["correct"], "steps": len(keep),
            "span_median_ms": {k: 1000 * sorted(v)[len(v) // 2]
                               for k, v in dur.items()},
            "span_mean_ms": {k: 1000 * sum(v) / len(v)
                             for k, v in dur.items()},
            "host_gap_ms": {"n": len(gaps),
                            "median": 1000 * gaps[len(gaps) // 2],
                            "mean": 1000 * sum(gaps) / len(gaps)}}


def dump(path: str, res) -> None:
    """The traced device ops and modules, the engine spans with their
    stats, times in seconds on the trace's clock, and the programs' name
    paths."""
    rows = [[e.plane, e.line, e.name, e.start, e.end, e.stats]
            for e in res["events"]
            if (e.plane.startswith(xtrace.DEVICE_PREFIX) and e.line in (
                xtrace.OPS_LINE, xtrace.MODULES_LINE))
            or (e.plane.startswith("/host:") and e.name in spans.SPANS)]
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"events": rows, "programs": res["programs"]}, f,
                  default=repr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if jax.devices()[0].platform != "tpu":
        print("spantrace: needs a TPU", file=sys.stderr)
        return 2
    if not args.profile:
        res, hub = host_run(cell, args.seed, args.seconds)
        print(json.dumps(host_summary(res, hub), default=repr), flush=True)
        return 0
    res, hub = traced_run(cell, args.seed, args.seconds)
    out = summary(res, hub)
    out["span_cost_us"] = span_cost_us()
    print(json.dumps(out, default=repr), flush=True)
    if args.dump:
        dump(args.dump, res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
