"""Chip benchmark of the MoE serving engine: one cell per run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``configs/<config>.json``: published sizes, the cut, slots and cache)
and a traffic mix (``traffic/<mix>.json``: lengths, rate, lead-in). The
harness finds both, and each per-layer metric (``metrics/<name>.py``), by
name; adding a cell, a configuration, a mix or a metric adds files only.

One run, in order:

1. refuse to run (exit 2, no result) unless JAX sees as many TPU chips as
   the cell asks for and ``peaks.py`` knows their kind;
2. set-up, timed as ``setup_s``: weights from the seed on the device in
   one jitted call, the engine (``ContinuousEngine``, or
   ``DistributedEngine`` over an expert-parallel mesh on four chips) on
   the kernel path, every program the cell uses warmed by a few requests,
   and every request of the run built;
3. a lead-in at the cell's rate (not timed) so occupancy is steady, then
   the window of ``--seconds``: requests submitted open-loop at their due
   times, the engine stepped, every token time-stamped; compilations
   inside the window are counted;
4. a bounded drain, so requests due late in the window get their first
   token (a request without one counts as missing);
5. with ``--trace 1`` a few seconds of the window run under the profiler
   and the per-layer metrics are read from the trace and the host
   records; otherwise the end-to-end metrics are computed;
6. the program's state is freed and a sample of finished requests, drawn
   from the seed with the longest among them, is checked against the
   float32 reference (``check.py``).

The last line of standard output is the result JSON; the numbers compared
are the last lines of standard error and the result's last key.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import check, traffic, weights, xtrace  # noqa: E402
from benchmarks.chip.peaks import UnknownDevice, peaks  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (``inf`` entries count as values)."""
    if not values:
        return float("nan")
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


# ---------------------------------------------------------------------------
# Finding a cell by name
# ---------------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = traffic.load_mix(w["traffic"], root / "benchmarks" / "chip")

    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    return {"workload": w, "cfg": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
            "per_layer": [m for m in bench["per_layer"] if listed(m)]}


def load_metric(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def program_config(cfg: dict):
    """The engine's ``ModelConfig`` for the sizes in ``cfg``."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    moe = dataclasses.replace(
        base.moe, n_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff=cfg["intermediate_size"],
        capacity_factor=cfg["capacity_factor"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], moe=moe)


def build(cfg: dict, chips: int, seed: int, devices, log=lambda m: None):
    """Weights and the engine a user would build for this deployment."""
    import jax
    from jax.sharding import NamedSharding

    from repro.models import Model
    from repro.serving import ContinuousEngine, DistributedEngine, EngineConfig

    pcfg = program_config(cfg)
    model = Model(pcfg)
    expect = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.generate(cfg, seed))
    if (jax.tree.structure(expect) != jax.tree.structure(got)
            or jax.tree.leaves(jax.tree.map(
                lambda a, b: a.shape != b.shape or a.dtype != b.dtype,
                expect, got)).count(True)):
        raise ValueError("the benchmark's weights do not match the engine's "
                         "parameter layout")
    serve = cfg["serving"]
    config = EngineConfig(prefill_chunk=serve["prefill_chunk"],
                          bucket_policy=f"step:{serve['prefill_chunk']}",
                          kernels=True)
    if chips == 1:
        params = jax.block_until_ready(weights.make(cfg, seed))
        log(f"weights: {weights.nbytes(params)} bytes")
        eng = ContinuousEngine(model, params, serve["slots"],
                               serve["cache_capacity"], config=config)
        return params, eng
    from repro.launch.mesh import make_ep_mesh
    from repro.sharding import param_specs

    mesh = make_ep_mesh(chips, devices=list(devices[:chips]))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(pcfg, mesh))
    params = jax.block_until_ready(weights.make(cfg, seed, shardings))
    log(f"weights: {weights.nbytes(params)} bytes over {chips} chips")
    eng = DistributedEngine(model, params, serve["slots"],
                            serve["cache_capacity"], mesh=mesh, config=config)
    return params, eng


def program_memory(eng) -> int | None:
    """Bytes the compiled decode program needs (arguments, outputs and
    scratch, less what is aliased), from ``memory_analysis()``; None where
    the engine keeps no private ``_decode`` or it is wrapped and cannot be
    lowered on its own."""
    import jax.numpy as jnp

    fn = getattr(eng, "_decode", None)
    if not hasattr(fn, "lower"):
        return None
    mask = jnp.ones((eng.batch_slots,), bool)
    m = fn.lower(eng.params, eng.tokens, eng.cache, mask).compile(
    ).memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# Host records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    chunk_real: int = 0        # real prompt tokens in this step's chunk
    chunk_start: int = 0       # their first position within the prompt
    chunk_last: bool = False   # the chunk ended its prompt
    ran_chunk: bool = False
    decode_active: int = 0     # slots that got a decode token
    decode_valid: int = 0      # their valid cache lengths, summed
    traced: bool = False


@dataclasses.dataclass
class Track:
    req: object
    due: float
    in_window: bool
    padded: int
    submitted: float | None = None
    first_chunk: float | None = None
    times: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    seen: int = 0
    slot: int | None = None


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads (``metrics/<name>.py``)."""
    cfg: dict
    mix: dict
    chips: int
    seconds: float
    peaks: dict | None
    steps: list
    tracks: list
    trace: dict | None = None          # plane -> xtrace.Device
    trace_window_s: float = 0.0

    def window_steps(self):
        return [s for s in self.steps if 0.0 <= s.t0 and s.t1 <= self.seconds]

    def traced_steps(self):
        return [s for s in self.steps if s.traced]

    def gaps(self):
        """Every token gap of one request inside the window, with the
        step that produced the later token."""
        out = []
        for t in self.tracks:
            for a, b, st in zip(t.times, t.times[1:], t.steps[1:]):
                if 0.0 <= a and b <= self.seconds:
                    out.append((b - a, st))
        return out


def in_flight(eng) -> dict:
    """``id(request) -> (slot, prompt tokens done)`` of the engine's
    in-flight chunked prefills. The engine keeps them in a private list of
    ``[request, slot, padded ids, done]``; a change of that layout stops
    the run here, where it would otherwise miscount the chunk records."""
    from repro.serving import Request

    out = {}
    for p in eng._pending:
        if not (isinstance(p, list) and len(p) == 4
                and isinstance(p[0], Request) and isinstance(p[1], int)
                and isinstance(p[3], int)):
            raise TypeError("the engine's in-flight prefill records are no "
                            "longer [request, slot, ids, done]; the "
                            "harness's chunk records would miscount")
        out[id(p[0])] = (p[1], p[3])
    return out


class Client:
    """The open-loop client: submits due requests between engine steps,
    steps the engine, and records every token and every step."""

    def __init__(self, eng, tracks, clock):
        self.eng, self.clock = eng, clock
        self.pending = sorted(tracks, key=lambda t: t.due)
        self.next = 0
        self.live: list[Track] = []
        self.steps: list[Step] = []
        self.lateness: list[float] = []

    def submit_due(self, now: float) -> None:
        while (self.next < len(self.pending)
               and self.pending[self.next].due <= now):
            t = self.pending[self.next]
            self.eng.submit(t.req)
            t.submitted = now
            if t.in_window:
                self.lateness.append(now - t.due)
            self.live.append(t)
            self.next += 1

    def submit_all(self, now: float) -> None:
        """A backlog: every request queued at once, before the window."""
        for t in self.pending[self.next:]:
            self.eng.submit(t.req)
            t.submitted = now
            self.live.append(t)
        self.next = len(self.pending)

    def step(self, traced: bool = False) -> bool:
        eng = self.eng
        before = {k: d for k, (_, d) in in_flight(eng).items()}
        t0 = self.clock()
        worked = eng.step()
        t1 = self.clock()
        rec = Step(t0, t1, traced=traced)
        flight = in_flight(eng)
        after = {k: d for k, (_, d) in flight.items()}
        seated = {id(r): i for i, r in enumerate(eng.slots) if r is not None}
        keep = []
        for t in self.live:
            n = len(t.req.out_tokens)
            k = id(t.req)
            if t.slot is None:
                t.slot = flight[k][0] if k in flight else seated.get(k)
            progressed = (k in after and after[k] > before.get(k, 0)) or (
                k in before and k not in after) or (t.seen == 0 and n > 0
                                                    and k not in before)
            if progressed and t.first_chunk is None:
                t.first_chunk = t0
            if progressed:
                lo = before.get(k, 0)
                hi = after.get(k, t.padded)
                pad = t.padded - len(t.req.prompt)
                real_lo, real_hi = max(lo, pad), max(hi, pad)
                rec.ran_chunk = True
                rec.chunk_real += real_hi - real_lo
                rec.chunk_start = real_lo - pad
                rec.chunk_last = hi >= t.padded
            if n > t.seen:
                # A first token comes from the prefill, the rest from decode.
                if n - t.seen - (t.seen == 0):
                    rec.decode_active += 1
                    # cache length after this decode: padded prompt plus
                    # the tokens fed back so far
                    rec.decode_valid += t.padded + n - 1
                t.times += [t1] * (n - t.seen)
                t.steps += [rec] * (n - t.seen)
                t.seen = n
            if n < t.req.max_new_tokens:
                keep.append(t)
        self.live = keep
        self.steps.append(rec)
        return worked

    def idle(self) -> bool:
        e = self.eng
        return not (e.queue or e.num_active or e.num_pending)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start=None,
             check_mode: str = "compare", keep: bool = False) -> dict:
    """One run of ``cell`` (``load_cell``'s dict). Returns the result.

    ``check_mode``: "compare" decides ``correct``; "control" decides it
    too and also judges the fp8 control put in the program's place, and
    returns the readings of both (limits are set from those); "off" skips
    the reference. ``keep`` returns the
    host records and trace events too (``ctx``, ``events``)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, mix, wl = cell["cfg"], cell["mix"], cell["workload"]
    chips = wl["chips"]
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    pk = peaks(devs[0].device_kind) if require_tpu else None
    used = list(devs[:chips])

    counts = {"compiles": 0, "traces": 0, "on": False}

    def on_event(name, _secs, **_kw):
        if counts["on"]:
            if name == COMPILE_EVENT:
                counts["compiles"] += 1
            elif name == TRACE_EVENT:
                counts["traces"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    def since() -> str:
        return f"[{time.perf_counter() - t_start:.2f} s]"

    serve = cfg["serving"]
    step_len = serve["prefill_chunk"]
    log(f"{since()} devices: {len(devs)} {devs[0].device_kind}")
    params, eng = build(cfg, chips, seed, used,
                        log=lambda m: log(f"{since()} {m}"))
    log(f"{since()} engine built")

    from repro.serving import Request

    offered = traffic.offered(mix, seed, seconds, cfg["vocab_size"])
    tracks = []
    for o in offered:
        p = traffic.padded(len(o.prompt), step_len)
        if p + o.out_len - 1 > serve["cache_capacity"]:
            raise ValueError(f"a {len(o.prompt)}-token prompt with "
                             f"{o.out_len} output tokens does not fit the "
                             f"cache of {serve['cache_capacity']}")
        tracks.append(Track(Request(prompt=o.prompt,
                                    max_new_tokens=o.out_len),
                            o.due, o.in_window, p))
    win = [t for t in tracks if t.in_window]
    log(f"offered: {len(win)} requests in the window, "
        f"{sum(len(t.req.prompt) for t in win)} prompt tokens, "
        f"{sum(t.req.max_new_tokens for t in win)} output tokens; "
        f"{len(tracks) - len(win)} in the lead-in")

    # Warm every program the traffic uses: a first chunk, a later chunk,
    # decode, and the admission's small ops.
    rng = np.random.default_rng(seed + 1)
    warm = [Request(prompt=rng.integers(1, cfg["vocab_size"], n,
                                        dtype=np.int32), max_new_tokens=3)
            for n in (step_len + 1, step_len // 2)]
    for r in warm:
        eng.submit(r)
    while not (not eng.queue and not eng.num_active and not eng.num_pending):
        eng.step()
    log(f"{since()} warmed")
    decode_bytes = program_memory(eng)
    jax.block_until_ready(eng.cache)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f} (weights, engine, warm-up"
        f"{'' if decode_bytes is None else ', decode program analysed'})")

    clock0 = [0.0]

    def clock():
        return time.perf_counter() - clock0[0]

    drv = Client(eng, tracks, clock)
    backlog = mix["arrivals"] == "backlog"
    # Set-up's garbage is collected now and its survivors frozen, so the
    # collector does not stall a step of the window to walk them.
    gc.collect()
    gc.freeze()
    clock0[0] = time.perf_counter() + mix["lead_in_s"]
    if backlog:
        drv.submit_all(clock())
    # Lead-in: requests due before 0, and the backlog's first ones.
    while clock() < 0.0:
        drv.submit_due(clock())
        if drv.idle():
            time.sleep(min(0.001, max(0.0, -clock())))
            continue
        drv.step()

    trace_dir, t_trace = None, None
    tr_from = mix["trace_offset_s"] if trace else math.inf
    tr_to = tr_from + mix["trace_seconds"]
    counts["on"] = True
    while True:
        now = clock()
        if now >= seconds:
            break
        if trace_dir is None and now >= tr_from:
            trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(trace_dir)
            t_trace = [clock(), None]
        if t_trace is not None and t_trace[1] is None and now >= tr_to:
            jax.block_until_ready(eng.cache)
            t_trace[1] = clock()
            jax.profiler.stop_trace()
        drv.submit_due(now)
        if drv.idle():
            nxt = (drv.pending[drv.next].due if drv.next < len(drv.pending)
                   else seconds)
            time.sleep(max(0.0, min(nxt, seconds) - clock()))
            continue
        drv.step(traced=t_trace is not None and t_trace[1] is None)
    counts["on"] = False
    gc.unfreeze()
    log(f"{since()} window closed")
    if t_trace is not None and t_trace[1] is None:
        jax.block_until_ready(eng.cache)
        t_trace[1] = clock()
        jax.profiler.stop_trace()
    window_compiles = dict(counts)

    # The requests the window attempted: those due in it (of a backlog,
    # those it started).
    started = [t for t in win if t.submitted is not None and (
        not backlog or (t.first_chunk is not None
                        and t.first_chunk < seconds))]
    # Drain: attempted requests still waiting for a first token, and,
    # until the check has enough finished requests to read, the rest.
    drain_end = seconds + mix["drain_s"]

    def finished_tokens():
        return sum(t.seen for t in win if t.seen >= t.req.max_new_tokens)

    while ((any(t.seen == 0 for t in started)
            or finished_tokens() < mix["check_tokens"])
           and clock() < drain_end and not drv.idle()):
        drv.step()

    log(f"{since()} drained")
    lateness = drv.lateness
    missing = [t for t in started if t.seen == 0]
    done = [t for t in tracks if t.seen >= t.req.max_new_tokens]
    log(f"compiles inside the window: {window_compiles['compiles']} "
        f"(traces {window_compiles['traces']})")
    log(f"requests: {len(win)} due in the window, {len(started)} submitted, "
        f"{sum(1 for t in win if t.seen >= t.req.max_new_tokens)} completed, "
        f"{len(missing)} missing a first token after a {mix['drain_s']} s "
        f"drain; {len(done)} finished in the whole run")
    if lateness:
        log(f"generator lateness: median {nearest_rank(lateness, 0.5):.6f} s"
            f", p99 {nearest_rank(lateness, 0.99):.6f} s, max "
            f"{max(lateness):.6f} s")

    host_steps(drv.steps, seconds)

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    limit = max(int((d.memory_stats() or {}).get("bytes_limit", 0))
                for d in used)
    log(f"peak memory: {peak} bytes in use of {limit} (memory_stats, "
        f"fullest chip); decode program {decode_bytes} bytes "
        f"(memory_analysis)")

    ctx = Context(cfg, mix, chips, seconds, pk, drv.steps, tracks)
    result = {"attempted": len(started), "failed": len(missing)}
    if trace:
        events = xtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = xtrace.devices(events)
        ctx.trace_window_s = t_trace[1] - t_trace[0]
        metrics = {}
        for m in cell["per_layer"]:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        devs_tr = list(ctx.trace.values())
        result["metrics"] = metrics
        busy = (sum(d.busy for d in devs_tr) / len(devs_tr)
                if devs_tr else 0.0)
        result["trace"] = {"busy_s": busy, "window_s": ctx.trace_window_s}
        if devs_tr:
            result["breakdown"] = {
                "device_ops": xtrace.top_ops(devs_tr[0]),
                "idle_gaps": xtrace.idle_gaps(devs_tr[0], events)}
        if keep:
            result["events"] = events
        del events
    else:
        result["metrics"] = end_to_end(ctx, cell["end_to_end"], setup_s)
    for m, v in result["metrics"].items():
        log(f"{m}: {v['value']} {v['unit']}")

    # Free the program's state before the reference runs.
    sample = check.sample([t for t in done if t.in_window] or done, seed,
                          mix["check_tokens"], serve["slots"])
    log(f"check sample: {len(sample)} requests in slots "
        f"{sorted(-1 if t.slot is None else t.slot for t in sample)}")
    inputs = [(check.padded_prompt(t.req.prompt, t.padded),
               list(t.req.out_tokens)) for t in sample]
    for x in jax.tree.leaves(eng.cache):
        x.delete()
    del eng, drv
    if check_mode == "compare":
        verdict = check.compare(cfg, params, inputs)
    elif check_mode == "control":
        v = check.compare(cfg, params, inputs, control=True)
        verdict = {"correct": v["correct"], "numbers": dict(
            v["readings"], program_correct=v["correct"],
            control_correct=v["control"]["correct"])}
    else:
        verdict = {"correct": None, "numbers": {}}
    log(f"{since()} checked")
    result.update(correct=verdict["correct"], peak=peak,
                  check=verdict["numbers"])
    if keep:
        result["ctx"] = ctx
    return result


def host_steps(steps: list, seconds: float) -> None:
    """How long the window's engine steps took on the host clock, those
    with a prefill chunk apart from those without."""
    win = [s for s in steps if 0.0 <= s.t0 and s.t1 <= seconds]
    for label, part in (("decode only", [s for s in win if not s.ran_chunk]),
                        ("with a chunk", [s for s in win if s.ran_chunk])):
        d = [s.t1 - s.t0 for s in part]
        if d:
            log(f"host steps {label}: {len(d)}, median "
                f"{1000 * nearest_rank(d, 0.5):.3f} ms, p90 "
                f"{1000 * nearest_rank(d, 0.9):.3f} ms, mean active "
                f"{sum(s.decode_active for s in part) / len(d):.2f}")


def end_to_end(ctx: Context, wanted: list, setup_s: float) -> dict:
    secs = ctx.seconds
    toks = sum(1 for t in ctx.tracks for x in t.times if 0.0 <= x <= secs)
    gaps = [g for g, _ in ctx.gaps()]
    win = [t for t in ctx.tracks if t.in_window and t.submitted is not None
           and t.due < secs]
    ttft = [(t.times[0] - t.due) if t.times else math.inf for t in win]
    values = {
        "setup_s": setup_s,
        "output_tok_per_s": toks / secs,
        "itl_p50_s": nearest_rank(gaps, 0.5),
        "itl_p99_s": nearest_rank(gaps, 0.99),
        "ttft_p50_s": nearest_rank(ttft, 0.5),
    }
    log(f"window: {toks} tokens, {len(gaps)} token gaps, {len(win)} "
        f"requests due")
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Every program goes into the cache, so a second run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except (NoChip, UnknownDevice) as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 2
    devs = jax.devices()[:cell["workload"]["chips"]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["peak"]}
    if args.trace:
        device.update(res["trace"])
    log(f"compile cache: {cache_dir}")
    for k, v in res["check"].items():
        print(f"check {k}: {v}", file=sys.stderr, flush=True)
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": device}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["check"] = res["check"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
