"""FLOP and byte counts at phi3.5-moe shapes against hand arithmetic, and
the peaks table."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip import peaks, work  # noqa: E402

CFG = json.loads((Path(__file__).resolve().parent / "configs"
                  / "phi35moe-1chip.json").read_text())


def test_moe_ffn_counts():
    # 48 routed rows, 16 experts hit: 48 * 3 matmuls * 2 * 4096 * 6400
    # FLOPs; 16 * 3 * 4096 * 6400 * 2 B of weights + 48 rows in and out.
    fl, by = work.moe_ffn(CFG, 48, 16)
    assert fl == 48 * 3 * 2 * 4096 * 6400 == 7_549_747_200
    assert by == 16 * 3 * 4096 * 6400 * 2 + 48 * 2 * 4096 * 2
    assert by == 2_517_368_832


def test_decode_attn_counts():
    # 24 slots holding 1000 valid positions each; 32 query heads of 128,
    # 8 kv heads: 4 * L * 32 * 128 FLOPs; K and V of 8 * 128 bf16 each.
    fl, by = work.decode_attn(CFG, 24 * 1000, 24)
    assert fl == 4 * 24_000 * 32 * 128
    assert by == 2 * 24_000 * 8 * 128 * 2 + 2 * 24 * 32 * 128 * 2


def test_token_flops():
    per_layer = (2 * 4096 * (32 + 16) * 128 + 2 * 32 * 128 * 4096
                 + 4 * 100 * 32 * 128 + 2 * 4096 * 16 + 2 * 6 * 4096 * 6400)
    assert work.token_flops(CFG, 100) == 4 * per_layer + 2 * 4096 * 32064
    assert work.token_flops(CFG, 100, logits=False) == 4 * per_layer


def test_prefill_flops_counts_context_and_one_head():
    one = work.prefill_flops(CFG, 0, 1, last=False)
    assert one == work.token_flops(CFG, 1, logits=False)
    two = work.prefill_flops(CFG, 0, 2, last=True)
    assert two == pytest.approx(work.token_flops(CFG, 1, False)
                                + work.token_flops(CFG, 2, False)
                                + 2 * 4096 * 32064)


def test_expected_distinct():
    assert work.expected_distinct(1, 16) == pytest.approx(1.0)
    assert work.expected_distinct(1000, 16) == pytest.approx(16.0)


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
