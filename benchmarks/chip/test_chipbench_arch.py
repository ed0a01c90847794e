"""The seam between the harness and an architecture (``arch/``). Every
module under ``arch/``, with each configuration file it serves, and the
test-only ``dense_shared_arch`` (two segments: a leading dense layer, then
MoE layers with a shared expert) keep the contract: the weights nest into
the program's parameter tree, the tiny cell runs ``correct`` through
``run.run_cell`` and its fp8 control does not. Phi-3.5-MoE's weights and
work counts equal the values recorded before the seam existed, and no
shared harness file reads an architecture's key."""

import hashlib
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from benchmarks.chip import arch, dense_shared_arch, peaks  # noqa: E402
from benchmarks.chip import run, tiny, weights, work, xtrace  # noqa: E402
from benchmarks.chip.xtrace import Event  # noqa: E402

IN_PACKAGE = arch.modules()
FILED = [json.loads(p.read_text())
         for p in sorted((HERE / "configs").glob("*.json"))]
CASES = ([(arch.find(c), c) for c in FILED]
         + [(dense_shared_arch, dense_shared_arch.CONFIG)])
IDS = [c["arch"] for _, c in CASES]


@pytest.fixture(autouse=True)
def find_sees_test_arch(monkeypatch):
    """``arch.find`` also finds the test-only architecture."""
    monkeypatch.setattr(arch, "modules",
                        lambda: (*IN_PACKAGE, dense_shared_arch))


def test_every_module_serves_a_configuration_file():
    assert IN_PACKAGE
    assert set(IN_PACKAGE) <= {arch.find(c) for c in FILED}


def test_find_names_the_unknown_id_and_the_known_ones():
    with pytest.raises(KeyError, match="no-such-arch") as e:
        arch.find({"arch": "no-such-arch"})
    assert "phi3.5-moe-42b-a6.6b" in str(e.value)
    assert arch.find(dense_shared_arch.CONFIG) is dense_shared_arch


@pytest.mark.parametrize("size", ["as_filed", "tiny"])
@pytest.mark.parametrize("mod,cfg", CASES, ids=IDS)
def test_nest_is_the_programs_parameter_tree(mod, cfg, size):
    import jax

    from repro.models import Model

    if size == "tiny":
        cfg = mod.tiny(cfg)
    want = jax.eval_shape(Model(mod.program_config(cfg)).init,
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.generate(cfg, 0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype), got, want)))
    flat = {p: p for p in mod.shapes(cfg)}
    assert sorted(jax.tree.leaves(mod.nest(flat))) == sorted(flat)


@pytest.mark.parametrize("mod,cfg", CASES, ids=IDS)
def test_tiny_cell_is_correct_and_its_fp8_control_is_not(mod, cfg):
    cell = tiny.cell(cfg=cfg)
    lim = cell["cfg"]["check"]
    r = run.run_cell(cell, 2**31 + 11, 2.0, False, require_tpu=False,
                     check_mode="control")
    c = r["check"]
    assert c["compared_tokens"] >= lim["min_compared_tokens"]
    assert r["correct"] is c["program_correct"] is True, c
    assert c["control_correct"] is False, c
    assert r["failed"] == 0


# Recorded with the harness as it was before the seam (commit 83926e5):
# sha256 over the leaves of weights.generate at the tiny size, and the
# work counts at the published widths.
PHI_WEIGHTS = {
    3: "216399ac1b95d737c57739b6f762b1ce85d23db2a816c7513959f56b9ee85215",
    2**32 + 5:
        "89b15fd6b90b4561f7df2077cac71c2d66a1fc939fa79474997f85c10be54b60",
}


@pytest.mark.parametrize("seed", sorted(PHI_WEIGHTS))
def test_phi_weights_as_before(seed):
    import jax

    h = hashlib.sha256()
    for x in jax.tree.leaves(weights.generate(tiny.cell()["cfg"], seed)):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == PHI_WEIGHTS[seed]


def test_phi_work_counts_as_before():
    cfg = tiny.config()
    assert work.moe_ffn(cfg, 37, 13.25) == (5819596800, 2084651008.0)
    assert work.moe_ffn(cfg, 48.0, work.expected_distinct(48, 16)) == (
        7549747200.0, 2403754682.3887897)
    assert work.decode_attn(cfg, 24_017, 24) == (393494528, 98766848)
    assert work.decode_attn(cfg, 9170.5, 9) == (150249472.0, 37709824.0)
    assert work.token_flops(cfg, 100) == 1863581696
    assert work.token_flops(cfg, 1087.25, logits=False) == 1665613824.0
    assert work.prefill_flops(cfg, 512, 512, True) == 842361470976.0
    assert work.prefill_flops(cfg, 0, 476, False) == 766355308544.0
    assert work.prefill_flops(cfg, 3, 0, True) == 0.0


def _decode_ctx(cfg):
    """Two traced decode steps of 10 active slots holding 10,000 valid
    positions, each in a 10 ms decode program with 4 ms of ``moe_gmm``
    and 2 ms of ``decode_attn``, on one chip."""
    d = "/device:TPU:0"
    ev = []
    for s in (0.0, 0.03):
        ev += [Event(d, "XLA Modules", "jit_decode_step(7)", s, s + 0.010),
               Event(d, "XLA Ops", "moe_gmm.3", s, s + 0.004),
               Event(d, "XLA Ops", "decode_attn.1", s + 0.008, s + 0.010)]
    steps = [run.Step(t, t + 0.02, decode_active=10, decode_valid=10_000,
                      traced=True) for t in (0.0, 0.03)]
    return run.Context(cfg=cfg, mix={}, chips=1, seconds=1.0,
                       peaks=peaks.peaks("TPU v5 lite"), steps=steps,
                       tracks=[], trace=xtrace.devices(ev),
                       trace_window_s=0.1)


def test_two_segments_and_layers_counted_by_kind():
    """The test-only architecture: 3 attention layers, of which the first
    has a dense FFN, so 2 MoE layers with a shared expert."""
    import jax

    cfg = dense_shared_arch.CONFIG
    params = jax.eval_shape(lambda: weights.generate(cfg, 0))
    assert len(params["segments"]) == 2
    assert weights.leaf(cfg, params, "dense/ffn/w_up").shape == (1, 256, 768)
    assert weights.leaf(cfg, params, "sparse/moe/shared/w_down").shape == (
        2, 384, 256)
    ctx = _decode_ctx(cfg)
    read = run.load_metric
    peak_f, peak_b = 197e12, 819e9
    fl = 2 * 3 * 4 * 10_000 * 4 * 64
    by = 2 * 3 * (2 * 10_000 * 2 * 64 * 2 + 2 * 10 * 4 * 64 * 2)
    assert read("decode_attn_roofline")(ctx) == pytest.approx(
        100 * max(fl / peak_f, by / peak_b) / 0.004)
    hit = 4 * (1 - (3 / 4) ** 20)
    fl = 2 * 2 * 20 * 6 * 256 * 512
    by = 2 * 2 * (hit * 3 * 256 * 512 * 2 + 20 * 2 * 256 * 2)
    assert read("moe_gmm_roofline.decode")(ctx) == pytest.approx(
        100 * max(fl / peak_f, by / peak_b) / 0.008)
    attn = 2 * 256 * 8 * 64 + 2 * 256 * 256 + 4 * 100 * 256
    ffn = 3 * 2 * 256 * 768
    moe = 2 * 256 * 4 + 2 * 6 * 256 * 512 + 6 * 256 * 384
    assert work.token_flops(cfg, 100) == (3 * attn + ffn + 2 * moe
                                          + 2 * 256 * 512)
    assert 0 < read("step_mfu.decode")(ctx) < 100


ALLOWED = {"arch", "serving", "check", "torch_dtype"}
PROSE = {"source", "reduced", "assumed", "deployment"}   # no code reads them


def test_no_shared_file_reads_an_architecture_key():
    keys = set(dense_shared_arch.CONFIG).union(*FILED) - ALLOWED - PROSE
    quoted = re.compile(r"""["'](%s)["']""" % "|".join(
        re.escape(k) for k in sorted(keys)))
    shared = [p for p in sorted(HERE.rglob("*.py"))
              if p.relative_to(HERE).parts[0] != "arch"
              and not p.name.startswith("test_")
              and p.name != "dense_shared_arch.py"]
    assert HERE / "run.py" in shared and HERE / "metrics" / (
        "moe_gmm_roofline.prefill.py") in shared
    found = [f"{p.relative_to(HERE)}:{i}: {m.group(0)}"
             for p in shared
             for i, line in enumerate(p.read_text().splitlines(), 1)
             for m in quoted.finditer(line)]
    assert not found, found
