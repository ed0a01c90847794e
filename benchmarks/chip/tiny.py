"""A cell at CPU size for the tests: a configuration file cut small by its
architecture's module (``arch/<module>.py``, ``tiny``: every width and
count, and the check limits set from readings at that size) and a traffic
mix with every length cut small, run with the same harness. The chip
cells' limits are set from chip readings in ``PERF.md``."""

from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip import arch

HERE = Path(__file__).resolve().parent


def config() -> dict:
    """``configs/phi35moe-1chip.json`` as filed."""
    return json.loads((HERE / "configs" / "phi35moe-1chip.json").read_text())


def cell(chips: int = 1, mix: str = "chat", cfg: dict | None = None) -> dict:
    """The tiny cell of ``cfg`` (a configuration as its file holds it;
    default ``configs/phi35moe-1chip.json``) under ``mix``."""
    cfg = cfg or config()
    cfg = arch.find(cfg).tiny(cfg)
    cfg["serving"] = {"slots": 4, "cache_capacity": 512, "prefill_chunk": 64}
    m = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    m.update(rate=3.0, lead_in_s=0.5, drain_s=60.0, trace_offset_s=0.2,
             trace_seconds=0.5, check_tokens=120,
             prompt={"median": 100, "sigma": 0.8, "min": 8, "max": 256},
             output={"median": 8, "sigma": 0.8, "min": 1, "max": 64})
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    return {"workload": {"name": f"tiny.{mix}", "chips": chips},
            "cfg": cfg, "mix": m, "end_to_end": bench["end_to_end"],
            "per_layer": [p for p in bench["per_layer"]
                          if p["source"] != "device_trace"]}
