"""A cell at CPU size for the tests: the phi3.5-moe configuration file
and a traffic mix with every width and length cut small, run with the
same harness. Its check limits are its own (the chip cells' limits are
set from chip readings in ``PERF.md``)."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def cell(chips: int = 1, mix: str = "chat") -> dict:
    cfg = json.loads((HERE / "configs" / "phi35moe-1chip.json").read_text())
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2,
               num_local_experts=4, vocab_size=512)
    cfg["serving"] = {"slots": 4, "cache_capacity": 512, "prefill_chunk": 64}
    # Readings at this size over seeds 1-6, 77 and 2**32 + 5: sound runs
    # 0 to 0.0227, the fp8 control 0.085 to 1.19 (test_chipbench_check).
    cfg["check"] = {"max_logit_gap": 0.045, "min_compared_tokens": 20,
                    "tie_margin": 0.05}
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
