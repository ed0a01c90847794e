"""A run whose timed path is broken underneath reports ``correct`` false:
once for each fault a served cell can have. The harness runs as it does
on the chip, its look for a chip skipped, at CPU size."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from benchmarks.chip import faults, run, tiny  # noqa: E402


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    # A backlog keeps every slot busy, so the faulty half of the batch
    # serves requests whatever the machine's speed.
    cell = tiny.cell()
    cell["mix"].update(arrivals="backlog", backlog_requests=16)
    r = run.run_cell(cell, 21, 2.0, False, require_tpu=False)
    assert r["correct"] is False, r["check"]


EP = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    from benchmarks.chip import run, tiny
    if sys.argv[1] == "exchange_left_out":
        import repro.distributed.alltoall as a2a
        a2a.ep_all_to_all = lambda buf, axis_names, rounds=None: buf
    r = run.run_cell(tiny.cell(chips=4), 5, 2.0, False, require_tpu=False)
    print(json.dumps({{"correct": r["correct"], "check": r["check"]}}))
""").format(root=str(ROOT), src=str(ROOT / "src"))


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange_left_out", False)])
def test_expert_parallel_exchange_left_out(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", EP, fault], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct, res
