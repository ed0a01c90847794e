"""The correctness check at CPU size: sound runs of the program pass, and
the fp8 control (the reference one precision step below bfloat16, put in
the program's place) fails, on several seeds. The harness runs as it does
on the chip, its look for a chip skipped."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from benchmarks.chip import run, tiny  # noqa: E402


@pytest.mark.parametrize("seed", [3, 2**32 + 5, 77])
def test_program_passes_and_fp8_control_fails(seed):
    cell = tiny.cell()
    lim = cell["cfg"]["check"]
    r = run.run_cell(cell, seed, 2.0, False, require_tpu=False,
                     check_mode="control")
    c = r["check"]
    assert c["compared_tokens"] >= lim["min_compared_tokens"]
    assert c["max_logit_gap"] <= lim["max_logit_gap"], c
    assert c["control_gap"] > lim["max_logit_gap"], c
    assert r["correct"] is c["program_correct"] is True
    assert c["control_correct"] is False
    assert r["failed"] == 0


def test_verdict_and_metrics_of_a_sound_run():
    r = run.run_cell(tiny.cell(mix="code"), 9, 2.0, False,
                     require_tpu=False)
    assert r["correct"] is True, r["check"]
    assert set(r["check"]) == {"max_logit_gap", "compared_tokens"}
    m = r["metrics"]
    assert set(m) == {"output_tok_per_s", "itl_p50_s",
                      "itl_p99_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


def test_backlog_keeps_slots_full():
    cell = tiny.cell()
    cell["mix"].update(arrivals="backlog", backlog_requests=24)
    cell["end_to_end"] = [m for m in cell["end_to_end"]
                          if m["name"] != "ttft_p50_s"]
    r = run.run_cell(cell, 13, 2.0, False, require_tpu=False, keep=True)
    assert r["correct"] is True, r["check"]
    assert run.load_metric("decode_occupancy")(r["ctx"]) > 50



def test_sample_reads_every_quarter_of_the_slots():
    from types import SimpleNamespace

    from benchmarks.chip import check

    done = [SimpleNamespace(slot=s, req=SimpleNamespace(
        out_tokens=[1] * (200 if s == 0 else 10))) for s in range(24)]
    for seed in (0, 1, 2**31 + 9):
        picked = check.sample(done, seed, 240, 24)
        assert picked[0].slot == 0
        assert {4 * t.slot // 24 for t in picked} == {0, 1, 2, 3}
        assert sum(len(t.req.out_tokens) for t in picked) >= 240


def test_engine_records_of_another_layout_stop_the_run():
    from types import SimpleNamespace

    with pytest.raises(TypeError):
        run.in_flight(SimpleNamespace(_pending=[("req", 0, None, 0)]))
