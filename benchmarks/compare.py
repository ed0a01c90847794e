"""Bench trend gate: diff two serving-bench JSON records across CI runs.

  PYTHONPATH=src python -m benchmarks.compare BASELINE.json NEW.json \
      [--threshold 0.2] [--summary trend.md]

Reads two ``BENCH_serving.json`` files (``serving_bench.py --json`` output),
extracts a fixed set of named metrics, prints a trend table, and — for the
metrics marked *gated* (absolute throughputs, plus the sweep section's
step-clock SLO attainments) — exits non-zero when any one regressed by more
than ``--threshold`` (default 20%). Ratio metrics (speedups, stall cuts,
predicted-time gains) are reported but not gated: they compare two legs
measured in the same process and are already machine-normalized, while
run-to-run throughput is the trajectory the ROADMAP wants guarded.

A top-level section in the NEW record that this table does not know also
fails the gate — an unknown section is a set of silently-ungated metrics,
so adding a bench section must come with its METRICS entries (or an
explicit KNOWN_SECTIONS listing).

The markdown table is appended to ``--summary`` when given, else to
``$GITHUB_STEP_SUMMARY`` when set (the Actions job summary).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _get(record: dict, path: str):
    """Fetch a dotted path from nested dicts; None when any hop is missing."""
    cur = record
    for key in path.split("."):
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur


def _tok_per_s(section: str, engine_key: str):
    def extract(record: dict):
        tok = _get(record, f"{section}.{engine_key}.tokens")
        wall = _get(record, f"{section}.{engine_key}.wall_s")
        if tok is None or wall is None or wall <= 0:
            return None
        return tok / wall
    return extract


# (name, extractor, higher_is_better, gated). Gated metrics are absolute
# throughputs — the regression the CI gate exists to catch.
METRICS = [
    # Only the prefill leg is gated: it times a ~32x larger token window
    # than the 8-token decode dispatch, whose wall-clock on 8 virtual CPU
    # devices sharing 2 runner cores is jitter-dominated (the overlap
    # section's own hard gate is OUTPUT IDENTITY, enforced via its "ok").
    ("overlap pipelined prefill tok/s",
     lambda r: _get(r, "overlap.pipelined.prefill_tok_per_s"), True, True),
    ("overlap sync prefill tok/s",
     lambda r: _get(r, "overlap.sync.prefill_tok_per_s"), True, False),
    ("overlap pipelined decode tok/s",
     lambda r: _get(r, "overlap.pipelined.decode_tok_per_s"), True, False),
    ("overlap decode speedup",
     lambda r: _get(r, "overlap.decode_speedup"), True, False),
    ("overlap prefill speedup",
     lambda r: _get(r, "overlap.prefill_speedup"), True, False),
] + [
    ("continuous tok/s", _tok_per_s("continuous", "continuous"), True, True),
    ("static tok/s", _tok_per_s("continuous", "static"), True, False),
    ("continuous wall speedup",
     lambda r: _get(r, "continuous.wall_speedup"), True, False),
    ("continuous step efficiency",
     lambda r: _get(r, "continuous.step_efficiency"), True, False),
    ("chunked stall cut", lambda r: _get(r, "chunked.stall_cut"), True, False),
    ("admission pooled tok/s", _tok_per_s("admission", "pooled"), True, True),
    ("admission serial tok/s", _tok_per_s("admission", "serial"), True, False),
    # TTFT cut is a same-process paired ratio — reported, not gated, like
    # the other speedups.
    ("admission ttft p95 cut",
     lambda r: _get(r, "admission.ttft_p95_cut"), True, False),
    ("drift adaptive gain", lambda r: _get(r, "drift.improvement"),
     True, False),
    ("kernel-path tok/s", lambda r: _get(r, "kernels.kernel.tok_per_s"),
     True, True),
    ("dense-path tok/s", lambda r: _get(r, "kernels.dense.tok_per_s"),
     True, False),
    ("kernel decode speedup", lambda r: _get(r, "kernels.decode_speedup"),
     True, False),
    ("skew replicated tok/s",
     lambda r: _get(r, "skew.replicated.tok_per_s"), True, True),
    ("skew unreplicated tok/s",
     lambda r: _get(r, "skew.static.tok_per_s"), True, False),
    ("skew replication gain (simulated)",
     lambda r: _get(r, "skew.improvement"), True, False),
    ("skew throughput ratio",
     lambda r: _get(r, "skew.throughput_ratio"), True, False),
] + [
    (f"multi N={n} tok/s",
     lambda r, n=n: _get(r, f"multi.tenants.{n}.engine.tok_per_s"),
     True, True)
    for n in (2, 3, 4)
] + [
    (f"multi N={n} aurora-vs-random gain",
     lambda r, n=n: _get(r, f"multi.tenants.{n}.gain"), True, False)
    for n in (2, 3, 4)
] + [
    # Four-scenario SLO sweep: attainment is measured on the deterministic
    # step clock, so it only moves when the SCHEDULE changes — gate it.
    # The sweep's wall-clock throughput stays informational (eight engine
    # legs in one process are re-jit dominated on CI runners).
    metric
    for cell in ("exclusive+homogeneous", "exclusive+heterogeneous",
                 "colocated+homogeneous", "colocated+heterogeneous")
    for metric in [
        (f"sweep {cell} ttft attainment",
         lambda r, c=cell: _get(r, f"sweep.scenarios.{c}.ttft_attainment"),
         True, True),
        (f"sweep {cell} tpot attainment",
         lambda r, c=cell: _get(r, f"sweep.scenarios.{c}.tpot_attainment"),
         True, True),
        (f"sweep {cell} tok/s",
         lambda r, c=cell: _get(r, f"sweep.scenarios.{c}.tok_per_s"),
         True, False),
    ]
] + [
    # Chaos section: the hard gates (both faults detected, lossless
    # byte-identical recovery, typed shed reasons, admitted-TTFT bound) live
    # in the section's own "ok" — serving_bench exits non-zero when they
    # fail, before compare.py ever runs. Here the shed leg's ADMITTED
    # throughput is trend-gated (shedding must protect admitted work, so a
    # drop means recovery or admission got slower); the mesh legs are
    # informational (an 8-virtual-device subprocess on 2 runner cores is
    # jitter-dominated, and its identity gate is the "ok").
    ("chaos shed admitted tok/s", _tok_per_s("chaos", "shed.shed"),
     True, True),
    ("chaos mesh faulted tok/s",
     lambda r: _get(r, "chaos.mesh.faulted.tok_per_s"), True, False),
    ("chaos mesh clean tok/s",
     lambda r: _get(r, "chaos.mesh.reference.tok_per_s"), True, False),
    ("chaos shed admitted ttft p95 (steps)",
     lambda r: _get(r, "chaos.shed.shed.ttft_p95_steps"), False, False),
    ("chaos shed count",
     lambda r: _get(r, "chaos.shed.shed.shed"), True, False),
]


# Sections the metric table knows how to read. Anything else appearing at
# the top level of a record FAILS the gate: a section this compare.py does
# not know is a section whose metrics are silently ungated, which is exactly
# the drift the gate exists to prevent — adding a bench section must come
# with its METRICS entries (or an explicit KNOWN_SECTIONS listing).
KNOWN_SECTIONS = {"admission", "chaos", "continuous", "chunked", "drift",
                  "kernels", "multi", "overlap", "skew", "sweep"}


def _section_rows(baseline: dict, new: dict):
    """Presence diff over top-level sections the metric table does NOT read.
    A section present in only the baseline is informational ("dropped" —
    the new run simply did not request it); a section the NEW run emits that
    this table cannot read is a hard failure row (its metrics would
    otherwise bypass the gate unreviewed). Known sections are covered
    metric-by-metric above, where one-sided values already render as
    "new"/"dropped"."""
    rows, unknown = [], []
    for key in sorted(set(baseline) | set(new)):
        if key in KNOWN_SECTIONS:
            continue
        if key not in new:
            rows.append((f"section '{key}'", None, None, None, "dropped"))
        else:
            rows.append((f"section '{key}'", None, None, None,
                         "UNRECOGNIZED"))
            unknown.append(key)
    return rows, unknown


def compare(baseline: dict, new: dict, threshold: float):
    """Returns (rows, regressions). rows: (name, old, new, delta, status)."""
    rows, regressions = [], []
    for name, extract, higher_better, gated in METRICS:
        old_v, new_v = extract(baseline), extract(new)
        if old_v is None and new_v is None:
            continue
        if old_v is None:
            rows.append((name, None, new_v, None, "new"))
            continue
        if new_v is None:
            rows.append((name, old_v, None, None, "dropped"))
            continue
        if old_v <= 0:
            # A non-positive baseline makes the relative delta meaningless
            # (sign flips); report the values without a trend verdict.
            rows.append((name, old_v, new_v, None, "n/a (baseline <= 0)"))
            continue
        delta = (new_v - old_v) / old_v
        change = delta if higher_better else -delta
        status = "ok"
        if gated and change < -threshold:
            status = "REGRESSED"
            regressions.append((name, old_v, new_v, delta))
        elif change < -threshold:
            status = "down (not gated)"
        rows.append((name, old_v, new_v, delta, status))
    section_rows, unknown = _section_rows(baseline, new)
    rows.extend(section_rows)
    for key in unknown:
        regressions.append((f"unrecognized section '{key}'",
                            None, None, None))
    return rows, regressions


def _fmt(v, width=10):
    return f"{'—':>{width}}" if v is None else f"{v:>{width}.3f}"


def render_text(rows) -> str:
    lines = [f"{'metric':<32} {'baseline':>10} {'current':>10} "
             f"{'Δ':>8}  status"]
    for name, old_v, new_v, delta, status in rows:
        d = "—" if delta is None else f"{delta:+.1%}"
        lines.append(f"{name:<32} {_fmt(old_v)} {_fmt(new_v)} {d:>8}  "
                     f"{status}")
    return "\n".join(lines)


def render_markdown(rows, threshold: float, regressions) -> str:
    lines = ["## Serving bench trend",
             "",
             f"Gate: >{threshold:.0%} regression on throughput metrics "
             "fails the job.",
             "",
             "| metric | baseline | current | Δ | status |",
             "|---|---:|---:|---:|---|"]
    for name, old_v, new_v, delta, status in rows:
        o = "—" if old_v is None else f"{old_v:.3f}"
        n = "—" if new_v is None else f"{new_v:.3f}"
        d = "—" if delta is None else f"{delta:+.1%}"
        badge = "❌" if status in ("REGRESSED", "UNRECOGNIZED") \
            else "✅" if status == "ok" else "ℹ️"
        lines.append(f"| {name} | {o} | {n} | {d} | {badge} {status} |")
    lines.append("")
    lines.append("**FAIL**: a gated check failed."
                 if regressions else "**PASS**: no gated regression.")
    return "\n".join(lines) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline", help="previous run's BENCH_serving.json")
    ap.add_argument("new", help="this run's BENCH_serving.json")
    ap.add_argument("--threshold", type=float, default=0.2,
                    help="relative throughput drop that fails the gate "
                         "(default 0.2 = 20%%)")
    ap.add_argument("--summary", default=None,
                    help="append the markdown table to this file "
                         "(default: $GITHUB_STEP_SUMMARY when set)")
    args = ap.parse_args()

    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.new) as f:
        new = json.load(f)

    rows, regressions = compare(baseline, new, args.threshold)
    print(render_text(rows))

    summary_path = args.summary or os.environ.get("GITHUB_STEP_SUMMARY")
    if summary_path:
        with open(summary_path, "a") as f:
            f.write(render_markdown(rows, args.threshold, regressions))

    if regressions:
        print(f"\nFAIL: {len(regressions)} gated check(s) failed "
              f"(threshold {args.threshold:.0%}):")
        for name, old_v, new_v, delta in regressions:
            if delta is None:
                print(f"  {name}: add METRICS entries (or list it in "
                      "KNOWN_SECTIONS) before gating can pass")
            else:
                print(f"  {name}: {old_v:.3f} -> {new_v:.3f} ({delta:+.1%})")
        return 1
    print(f"\nPASS: no gated metric regressed past {args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
