"""The reduction from a profiler trace to device numbers: on a slice
recorded on a TPU v5e (``testdata/trace_v5e_chat.json``) and on a small
hand-made trace: busy union, idle share, programs, kernel time,
collectives by op kind, idle gaps by host activity, and the metric
readers built on them."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from benchmarks.chip import peaks, run, xtrace  # noqa: E402
from benchmarks.chip.xtrace import Event  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _trace():
    ev = []
    for d in (D0, D1):
        # Two decode programs of 10 ms and one prefill chunk of 20 ms.
        for s in (0.000, 0.030):
            ev.append(Event(d, "XLA Modules", "jit_decode_step(7)", s,
                            s + 0.010))
            ev += [Event(d, "XLA Ops", "moe_gmm.3", s, s + 0.004),
                   Event(d, "XLA Ops", "fusion.1", s + 0.003, s + 0.006),
                   Event(d, "XLA Ops", "collective-permute-start.2",
                         s + 0.006, s + 0.007),
                   Event(d, "XLA Ops", "all-to-all.5", s + 0.007, s + 0.008),
                   Event(d, "XLA Ops", "decode_attn.1", s + 0.008,
                         s + 0.010)]
        ev.append(Event(d, "XLA Modules", "jit_prefill_chunk_slot(9)",
                        0.050, 0.070))
        ev += [Event(d, "XLA Ops", "moe_gmm.4", 0.050, 0.065),
               Event(d, "XLA Ops", "copy.1", 0.065, 0.070)]
    ev += [Event("/host:CPU", "python", "step", 0.0, 0.1),
           Event("/host:CPU", "python", "_value", 0.011, 0.029),
           Event("/host:CPU", "python", "submit", 0.071, 0.099)]
    return ev


def test_union_and_busy():
    assert xtrace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    devs = xtrace.devices(_trace())
    assert set(devs) == {D0, D1}
    assert devs[D0].busy == pytest.approx(0.040)


def test_programs_kernels_collectives():
    d = xtrace.devices(_trace())[D0]
    assert xtrace.module_seconds(d, ("decode_step",)) == pytest.approx(
        (0.020, 2))
    assert xtrace.op_seconds(d, lambda n: "moe_gmm" in n,
                             ("decode_step",)) == pytest.approx(0.008)
    assert xtrace.op_seconds(d, lambda n: "moe_gmm" in n,
                             ("prefill_chunk_slot",)) == pytest.approx(0.015)
    assert xtrace.op_seconds(d, xtrace.is_collective) == pytest.approx(0.004)
    assert not xtrace.is_collective("fusion.7")
    assert xtrace.top_ops(d, 1) == [["jit_prefill_chunk_slot/moe_gmm.4",
                                     pytest.approx(0.015)]]


def test_idle_gaps_by_host_activity():
    ev = _trace()
    gaps = dict(xtrace.idle_gaps(xtrace.devices(ev)[D0], ev))
    assert gaps["_value"] == pytest.approx(0.020)
    assert gaps["step"] == pytest.approx(0.010)


def _ctx():
    ctx = run.Context(cfg=run.json.loads(
        (ROOT / "benchmarks/chip/configs/phi35moe-1chip.json").read_text()),
        mix={}, chips=2, seconds=1.0, peaks=peaks.peaks("TPU v5 lite"),
        steps=[run.Step(0.0, 0.02, decode_active=10, decode_valid=10_000,
                        traced=True),
               run.Step(0.03, 0.05, decode_active=10, decode_valid=10_000,
                        traced=True),
               run.Step(0.05, 0.07, chunk_real=400, chunk_start=0,
                        chunk_last=True, ran_chunk=True, traced=True)],
        tracks=[], trace=xtrace.devices(_trace()), trace_window_s=0.1)
    return ctx


def test_metric_readers():
    ctx = _ctx()
    read = run.load_metric
    assert read("decode_step_ms")(ctx) == pytest.approx(10.0)
    assert read("device_idle_share")(ctx) == pytest.approx(60.0)
    assert read("exchange_ms.decode")(ctx) == pytest.approx(2.0)
    assert read("prefill_ms_per_ktok")(ctx) == pytest.approx(50.0)
    # decode_attn: 2 steps x 4 layers x 10,000 valid positions of 8 kv
    # heads x 128 x 2 (K, V) x 2 B + queries and outputs, over two chips,
    # against 4 ms of kernel per chip.
    by = 2 * 4 * (2 * 10_000 * 8 * 128 * 2 + 2 * 10 * 32 * 128 * 2)
    fl = 2 * 4 * 4 * 10_000 * 32 * 128
    want = 100 * max(fl / 197e12, by / 819e9) / 2 / 0.004
    assert read("decode_attn_roofline")(ctx) == pytest.approx(want)
    # moe_gmm: 20 routed rows per step hitting 16(1 - (15/16)^20) experts
    # on average, 8 ms of kernel per chip.
    hit = 16 * (1 - (15 / 16) ** 20)
    by = 2 * 4 * (hit * 3 * 4096 * 6400 * 2 + 20 * 2 * 4096 * 2)
    fl = 2 * 4 * 20 * 6 * 4096 * 6400
    want = 100 * max(fl / 197e12, by / 819e9) / 2 / 0.008
    assert read("moe_gmm_roofline.decode")(ctx) == pytest.approx(want)
    assert 0 < read("step_mfu.decode")(ctx) < 100


def test_readers_are_silent_without_their_source():
    ctx = _ctx()
    ctx.trace = {}
    for name in ("decode_step_ms", "device_idle_share", "exchange_ms.decode",
                 "moe_gmm_roofline.decode", "step_mfu.prefill"):
        assert run.load_metric(name)(ctx) is None


def _recorded():
    d = json.loads((ROOT / "benchmarks/chip/testdata/trace_v5e_chat.json"
                    ).read_text())
    return [Event(p, ln, xtrace.op_name(n), s * 1e-6, e * 1e-6)
            for p, ln, n, s, e in d["events"]]


def test_recorded_v5e_slice():
    ev = _recorded()
    dev = xtrace.devices(ev)[D0]
    # One 512-token prefill chunk (the engine's unnamed partial) and two
    # decode steps.
    t_pre, n_pre = xtrace.module_seconds(dev, ("prefill", "_unknown"))
    t_dec, n_dec = xtrace.module_seconds(dev, ("decode_step",))
    assert (n_pre, n_dec) == (1, 2)
    assert t_pre == pytest.approx(0.09719, rel=1e-3)
    assert t_dec == pytest.approx(2 * 0.07023, rel=1e-3)
    # The busy union, against a count on a 1-microsecond grid.
    ops = [e for e in ev if e.plane == D0 and e.line == "XLA Ops"]
    grid = set()
    for o in ops:
        grid.update(range(round(o.start * 1e6), round(o.end * 1e6)))
    assert dev.busy == pytest.approx(len(grid) * 1e-6, rel=1e-2)
    # The layer loop holds the other ops and is no leaf; the kernels are.
    loops = [o for o in dev.ops if o.name.startswith("while")]
    assert loops and not any(o.leaf for o in loops)
    gmm_pre = xtrace.op_seconds(dev, lambda n: "moe_gmm" in n,
                                ("prefill", "_unknown"))
    gmm_dec = xtrace.op_seconds(dev, lambda n: "moe_gmm" in n,
                                ("decode_step",))
    assert 0 < gmm_pre < t_pre and 0 < gmm_dec < t_dec
    attn = xtrace.op_seconds(dev, lambda n: "decode_attn" in n,
                             ("decode_step",))
    assert 0 < attn < t_dec
    assert xtrace.op_seconds(dev, lambda n: "decode_attn" in n,
                             ("prefill", "_unknown")) == 0
    assert xtrace.op_seconds(dev, xtrace.is_collective) == 0
    top = xtrace.top_ops(dev, 3)
    assert all("while" not in name for name, _ in top)
    assert sum(v for _, v in top) <= t_pre + t_dec


def test_op_name_is_the_ops_own():
    text = ("%fusion.3 = bf16[16,24,4096]{2,1,0} fusion(%moe_gmm.6), "
            "kind=kLoop")
    assert xtrace.op_name(text) == "fusion.3"
    assert xtrace.op_name("jit_decode_step(1)") == "jit_decode_step(1)"
