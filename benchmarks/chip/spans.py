"""The engine's spans and the model's scopes in a profiler trace.

A ``Telemetry(jax_profiler=True)`` hub attached to the engine writes each
of its spans into the trace as a host event of the same name, with the
span's attributes as event stats, on the device trace's clock. This
module keeps those stats, which ``xtrace.load`` drops, and turns the
events into span records.

The model names its device work with ``jax.named_scope``, which lands in
each HLO instruction's metadata (``op_name="jit(decode_step)/.../moe/
experts/..."``). A TPU op event of the trace carries the instruction's
name and no metadata, so the name paths are read from the compiled
program's HLO text (``op_paths``), the program the trace ran, and each
device leaf op is grouped by its innermost model scope (``scoped``).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

from benchmarks.chip import reading, xtrace

# Span names of ``repro.serving.engine.ContinuousEngine``.
SPANS = ("engine_step", "admit", "prefill", "prefill_chunk", "decode_step",
         "pool_step", "sample", "readback", "emit", "first_token")
# Where a program dispatch ends and the device may start the step's work.
DISPATCH = ("prefill", "prefill_chunk", "decode_step", "pool_step")
# Scopes the model sets (``jax.named_scope``), innermost wins.
SCOPES = ("layer_cache", "layer_weights", "attn", "attn/cache_write",
          "lm_head", "moe/router", "moe/dispatch", "moe/experts",
          "moe/exchange", "moe/combine")
UNSCOPED = "unscoped"


@dataclasses.dataclass
class StatEvent(xtrace.Event):
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Span:
    name: str
    start: float          # seconds on the trace's clock
    end: float
    attrs: dict


def load(log_dir: str) -> list[StatEvent]:
    """``xtrace.load`` that keeps the stats of the host events named by an
    engine span: the span's attributes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                out.append(StatEvent(
                    plane.name, line.name, xtrace.op_name(ev.name), s,
                    s + ev.duration_ns * 1e-9,
                    dict(ev.stats) if host and ev.name in SPANS else {}))
    return out


def spans(events) -> list[Span]:
    """The engine's spans, in start order (host events only)."""
    return sorted((Span(e.name, e.start, e.end, dict(e.stats))
                   for e in events if e.plane.startswith("/host:")
                   and e.name in SPANS), key=lambda s: (s.start, -s.end))


def innermost_scope(path: str) -> str:
    """The model scope that ends last in an op's name path (the longer one
    where two end together: ``attn/cache_write`` inside ``attn``)."""
    parts = path.split("/")
    best, best_end = UNSCOPED, -1
    for scope in SCOPES:
        k = scope.split("/")
        for i in range(len(parts) - len(k), -1, -1):
            if parts[i:i + len(k)] == k:
                end = i + len(k) - 1
                if end > best_end or (end == best_end
                                      and len(k) > best.count("/") + 1):
                    best, best_end = scope, end
                break
    return best


def op_paths(hlo_text: str) -> tuple[str, dict[str, str]]:
    """A compiled program's name (``jit_decode_step``) and, for each of its
    instructions, the name path of its metadata ("" where it has none:
    copies and buffers the compiler inserts)."""
    module = re.search(r"^HloModule ([\w.\-]+)", hlo_text, re.M)
    paths = {}
    for m in re.finditer(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$", hlo_text,
                         re.M):
        op = re.search(r'op_name="([^"]*)"', m.group(2))
        paths[m.group(1)] = op.group(1) if op else ""
    return (module.group(1) if module else ""), paths


def scoped(events, programs) -> dict[str, list[tuple[xtrace.Op, str]]]:
    """Per TPU plane: each op of ``xtrace.devices`` with its scope, looked
    up by name in its program's ``op_paths`` (``programs``: program name
    -> paths); an op of a program not given, or with no path, is
    unscoped."""
    def scope(o):
        paths = programs.get(o.module.split("(")[0], {})
        return innermost_scope(paths.get(o.name, ""))

    return {plane: [(o, scope(o)) for o in dev.ops]
            for plane, dev in xtrace.devices(events).items()}


def scope_seconds(ops, programs) -> dict[str, float]:
    """Leaf-op device time by scope, inside the named programs."""
    acc = collections.Counter()
    for o, scope in ops:
        if o.leaf and xtrace.in_programs(o.module, programs):
            acc[scope] += o.end - o.start
    return dict(acc)


def steps(span_list) -> list[dict]:
    """Each ``engine_step`` with its child spans, in order: ``{"step":
    Span, "children": [Span, ...]}``."""
    out = []
    tops = [s for s in span_list if s.name == "engine_step"]
    kids = [s for s in span_list if s.name != "engine_step"]
    j = 0
    for top in tops:
        while j < len(kids) and kids[j].start < top.start:
            j += 1
        children = []
        while j < len(kids) and kids[j].start <= top.end:
            children.append(kids[j])
            j += 1
        out.append({"step": top, "children": children})
    return out


def host_gaps(span_list) -> list[float]:
    """For each pair of consecutive engine steps that both decode: the
    seconds from the end of the first's ``readback`` to the end of the
    second's first program dispatch. A step that does not decode follows
    an engine with no slot busy, whose wait for arrivals is no host work
    between device steps."""
    st = steps(span_list)
    gaps = []
    for a, b in zip(st, st[1:]):
        back = [c for c in a["children"] if c.name == "readback"]
        disp = [c for c in b["children"] if c.name in DISPATCH]
        if back and any(c.name == "decode_step" for c in disp):
            gaps.append(disp[0].end - back[-1].end)
    return gaps


def idle_by_span(dev: xtrace.Device, span_list) -> dict[str, float]:
    """The device's idle time between its first and last op, by the
    innermost engine span the host was in (``between steps`` outside every
    ``engine_step``; ``outside`` before the first or after the last)."""
    gaps, last = [], None
    for s, e in sorted((o.start, o.end) for o in dev.ops):
        if last is not None and s > last:
            gaps.append((last, s))
        last = e if last is None else max(last, e)
    tops = [s for s in span_list if s.name == "engine_step"]
    lo, hi = (tops[0].start, tops[-1].end) if tops else (0.0, 0.0)
    acc = collections.Counter()
    active, i = [], 0
    for g0, g1 in gaps:                    # a sweep: gaps in time order
        while i < len(span_list) and span_list[i].start < g1:
            active.append(span_list[i])
            i += 1
        active = [sp for sp in active if sp.end > g0]
        # Cut the gap at every span edge inside it, then name each piece
        # by the shortest span covering its middle.
        cuts = sorted({g0, g1} | {t for sp in active
                                  for t in (sp.start, sp.end) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [sp for sp in active if sp.start <= mid <= sp.end]
            if cover:
                name = min(cover, key=lambda sp: sp.end - sp.start).name
            elif lo < mid < hi:
                name = "between steps"
            else:
                name = "outside"
            acc[name] += b - a
    return dict(acc)


def decode_scope_ms(ctx, match) -> float | None:
    """Device time per decode execution of the leaf ops whose scope
    satisfies ``match``, ms, mean over the traced chips; None without
    scoped ops or where no op carries such a scope."""
    per_plane = getattr(ctx, "scoped", None)
    t = reading.program_time(ctx, reading.DECODE)
    if not per_plane or t is None or not t[1]:
        return None
    secs = [sum(v for k, v in scope_seconds(ops, reading.DECODE).items()
                if match(k)) for ops in per_plane.values()]
    if not any(secs):
        return None
    return 1000.0 * sum(secs) / len(secs) / t[1]
