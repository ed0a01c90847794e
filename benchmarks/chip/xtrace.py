"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

``load`` flattens the trace into plain event records; everything else
works on those records, so the reduction can be checked on a small
recorded trace without a chip. On a TPU each chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds every operation that ran
and ``XLA Modules`` the compiled programs (``jit_<function>``) that
contain them. Host threads are planes ``/host:...``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_KINDS = ("collective-permute", "all-to-all", "all-gather",
                    "all-reduce", "reduce-scatter")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float          # seconds on the trace's clock
    end: float


def load(log_dir: str) -> list[Event]:
    """Every event of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                out.append(Event(plane.name, line.name, op_name(ev.name), s,
                                 s + ev.duration_ns * 1e-9))
    return out


def op_name(text: str) -> str:
    """A TPU op event is named by its HLO text (``%moe_gmm.6 = bf16[...]
    custom-call(...)``): keep the op's own name (``moe_gmm.6``), so that
    an operand's name never matches."""
    return text.split(" = ")[0].lstrip("%")


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class Op:
    name: str
    module: str            # the program the op ran in ("" if none)
    start: float
    end: float
    leaf: bool = True      # False for an op that holds others (a loop)


@dataclasses.dataclass
class Device:
    ops: list[Op]
    modules: list[Event]

    @property
    def busy(self) -> float:
        return union_seconds((o.start, o.end) for o in self.ops)


def devices(events: list[Event]) -> dict[str, Device]:
    """Per TPU plane: its ops, each tagged with the module that holds it
    (the module event on the same plane whose span contains the op)."""
    by_plane = collections.defaultdict(lambda: ([], []))
    for ev in events:
        if not ev.plane.startswith(DEVICE_PREFIX):
            continue
        if ev.line == OPS_LINE:
            by_plane[ev.plane][0].append(ev)
        elif ev.line == MODULES_LINE:
            by_plane[ev.plane][1].append(ev)
    out = {}
    for plane, (ops, mods) in sorted(by_plane.items()):
        mods.sort(key=lambda m: m.start)
        ops.sort(key=lambda o: (o.start, -o.end))
        tagged, j = [], 0
        for o in ops:
            while j < len(mods) and mods[j].end < o.start:
                j += 1
            inside = j < len(mods) and mods[j].start <= o.start
            tagged.append(Op(o.name, mods[j].name if inside else "",
                             o.start, o.end))
        for a, b in zip(tagged, tagged[1:]):
            if b.start < a.end and b.end <= a.end:
                a.leaf = False
        out[plane] = Device(tagged, mods)
    return out


def in_programs(module: str, programs) -> bool:
    """Whether the module (``jit_decode_step(123)``) is one of the
    programs, each given by a part of its name."""
    name = module.split("(")[0]
    return any(p in name for p in programs)


def module_seconds(dev: Device, programs) -> tuple[float, int]:
    """Total time and count of the executions of the named programs."""
    hit = [m for m in dev.modules if in_programs(m.name, programs)]
    return sum(m.end - m.start for m in hit), len(hit)


def op_seconds(dev: Device, match, programs=None) -> float:
    """Time of the ops (leaves only) whose name satisfies ``match``, inside
    the named programs when given."""
    return sum(o.end - o.start for o in dev.ops if o.leaf and match(o.name)
               and (programs is None or in_programs(o.module, programs)))


def is_collective(name: str) -> bool:
    return name.split(".")[0].removesuffix("-start").removesuffix(
        "-done") in COLLECTIVE_KINDS


def top_ops(dev: Device, n: int = 10) -> list:
    """The ``n`` leaf ops (by program and name) that took most time."""
    acc = collections.Counter()
    for o in dev.ops:
        if o.leaf:
            acc[f"{o.module.split('(')[0]}/{o.name}"] += o.end - o.start
    return [[k, v] for k, v in acc.most_common(n)]


def idle_gaps(dev: Device, events: list[Event], n: int = 10) -> list:
    """The device's idle gaps, summed by what the host was doing: the
    innermost host event that covers each gap's middle."""
    host = sorted((e for e in events if e.plane.startswith("/host:")
                   and e.end > e.start), key=lambda e: e.start)
    gaps, last = [], None
    for s, e in sorted((o.start, o.end) for o in dev.ops):
        if last is not None and s > last:
            gaps.append((last, s))
        last = e if last is None else max(last, e)
    acc = collections.Counter()
    active, i = [], 0
    for s, e in gaps:                      # a sweep: gaps in time order
        mid = (s + e) / 2
        while i < len(host) and host[i].start <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h.end >= mid]
        name = (min(active, key=lambda h: h.end - h.start).name
                if active else "(no host event)")
        acc[name] += e - s
    return [[k, v] for k, v in acc.most_common(n)]
