"""Readings that set a cell's rate and its correctness limits, on the chip.
Not part of a benchmark run.

    # the knee: one open-loop window per rate, no reference check
    python3 benchmarks/chip/calibrate.py sweep --workload W --seed N \
        --seconds S --rates 0.5,1,1.5
    # correctness readings of the program and of the fp8 control, per seed
    python3 benchmarks/chip/calibrate.py control --workload W \
        --seconds S --seeds 1,2,3 [--no-control]
    # a fault planted under the timed path (faults.py), judged per seed
    python3 benchmarks/chip/calibrate.py fault --workload W \
        --seconds S --seeds 1,2,3 --fault half_batch_left_out
    # the trace's planes, lines and a slice of device events, as JSON
    python3 benchmarks/chip/calibrate.py trace --workload W --seed N \
        --seconds S --out trace_sample.json

Each prints one JSON line per reading.
"""

from __future__ import annotations

import argparse
import collections
import copy
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip import run  # noqa: E402


def _offered_tok_per_s(cell, seed, seconds):
    from benchmarks.chip import traffic

    win = [o for o in traffic.offered(cell["mix"], seed, seconds,
                                      cell["cfg"]["vocab_size"])
           if o.in_window]
    return sum(o.out_len for o in win) / seconds


def sweep(cell, args):
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c["mix"]["rate"] = rate
        c["end_to_end"] = [{"name": n, "unit": "s"} for n in (
            "output_tok_per_s", "ttft_p50_s", "itl_p50_s", "itl_p99_s",
            "setup_s")]
        t = time.perf_counter()
        res = run.run_cell(c, args.seed, args.seconds, False,
                           check_mode="off", keep=True)
        ctx = res["ctx"]
        occ = run.load_metric("decode_occupancy")(ctx)
        stall = run.load_metric("stalled_gap_share")(ctx)
        qw = run.load_metric("queue_wait_ms")(ctx)
        print(json.dumps({
            "rate": rate, "offered_tok_per_s": _offered_tok_per_s(
                c, args.seed, args.seconds),
            **{k: v["value"] for k, v in res["metrics"].items()},
            "occupancy": occ, "stalled_gap_share": stall,
            "queue_wait_p90_ms": qw, "failed": res["failed"],
            "attempted": res["attempted"],
            "wall_s": time.perf_counter() - t}), flush=True)
        del res, ctx
        gc.collect()


def control(cell, args):
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = run.run_cell(cell, seed, args.seconds, False,
                           check_mode="compare" if args.no_control
                           else "control")
        print(json.dumps({"seed": seed, "check": res["check"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "wall_s": time.perf_counter() - t}), flush=True)
        del res
        gc.collect()


def fault(cell, args):
    from benchmarks.chip import faults

    faults.plant(faults.Patch, args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"], "check": res["check"],
                          "failed": res["failed"]}), flush=True)
        del res
        gc.collect()


def trace(cell, args):
    res = run.run_cell(cell, args.seed, args.seconds, True,
                       check_mode="off", keep=True)
    ev = res["events"]
    lines = collections.Counter((e.plane, e.line) for e in ev)
    dev0 = [e for e in ev if e.plane.startswith("/device:TPU:0")]
    t0 = min((e.start for e in dev0), default=0.0)
    sample = [[e.plane, e.line, e.name, e.start - t0, e.end - t0]
              for e in dev0 if e.start - t0 < args.slice_s]
    host = [[e.plane, e.line, e.name, e.start - t0, e.end - t0]
            for e in ev if e.plane.startswith("/host:")
            and 0 <= e.start - t0 < 0.05][:2000]
    out = {"lines": [[p, ln, n] for (p, ln), n in lines.items()],
           "modules": sorted({e.name for e in ev
                              if e.line == "XLA Modules"})[:200],
           "metrics": res["metrics"], "trace": res["trace"],
           "breakdown": res.get("breakdown"),
           "device0_slice": sample, "host_slice": host}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps({"metrics": res["metrics"], "trace": res["trace"],
                      "breakdown": res.get("breakdown"),
                      "lines": out["lines"][:60]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "control", "fault", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", default="1")
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--fault", default="half_batch_left_out")
    ap.add_argument("--out", default="trace_sample.json")
    ap.add_argument("--slice-s", type=float, default=0.5)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    {"sweep": sweep, "control": control, "fault": fault,
     "trace": trace}[args.mode](
        cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
