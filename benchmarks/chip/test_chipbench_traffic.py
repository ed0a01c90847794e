"""The load generator offers the same work for every seed: the same
requests, lengths and due times; the seed draws the token ids."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip import traffic  # noqa: E402

SEEDS = (0, 1, 2**31 + 12345, 2**33 + 7)


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_same_work_any_seed_other_ids(mix):
    m = traffic.load_mix(mix)
    runs = [traffic.offered(m, s, 50.0, 32064) for s in SEEDS]
    win = [[o for o in r if o.in_window] for r in runs]
    assert {len(w) for w in win} == {round(m["rate"] * 50.0)}
    assert len({len(r) for r in runs}) == 1
    shape = {tuple((round(o.due, 9), len(o.prompt), o.out_len) for o in r)
             for r in runs}
    assert len(shape) == 1
    ids = {tuple(tuple(o.prompt[:4].tolist()) for o in r) for r in runs}
    assert len(ids) == len(SEEDS)
    for w in win:
        assert all(0.0 <= o.due < 50.0 for o in w)
        assert all(1 <= t < 32064 for o in w for t in o.prompt)
    # Gaps, and lengths where they vary, are mixed over the window, not
    # sorted.
    gaps = np.diff([o.due for o in win[0]]).tolist()
    assert gaps != sorted(gaps) and gaps != sorted(gaps, reverse=True)
    spread = dict(m, output={"median": 100, "sigma": 1.0, "min": 1,
                             "max": 1000})
    outs = [o.out_len for o in traffic.offered(spread, SEEDS[0], 50.0, 32064)
            if o.in_window]
    assert outs != sorted(outs) and outs != sorted(outs, reverse=True)
    again = traffic.offered(m, SEEDS[2], 50.0, 32064)
    assert [o.prompt.tolist() for o in again] == [
        o.prompt.tolist() for o in runs[2]]


def test_lengths_are_quantiles_and_clipped():
    d = {"median": 1020, "sigma": 0.9, "min": 16, "max": 3072}
    n = 1000
    p = sorted(traffic._quantile_lengths(d, n))
    assert p[n // 2] == pytest.approx(d["median"], rel=0.01)
    assert p[0] >= d["min"] and p[-1] <= d["max"]


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_published_medians_are_the_lengths(mix):
    m = traffic.load_mix(mix)
    win = [o for o in traffic.offered(m, 5, 50.0, 32064) if o.in_window]
    assert {len(o.prompt) for o in win} == {m["prompt"]["fixed"]}
    assert {o.out_len for o in win} == {m["output"]["fixed"]}


def test_quantile_table_is_read_between_points():
    d = {"quantiles": [[0.0, 100], [0.5, 1000], [1.0, 3000]]}
    assert traffic._quantile_lengths(d, 2).tolist() == [550, 2000]
    with pytest.raises(ValueError):
        traffic._quantile_lengths({"quantiles": [[0.1, 5], [1.0, 9]]}, 3)


def test_backlog_queues_everything_at_once():
    m = dict(traffic.load_mix("chat"), arrivals="backlog",
             backlog_requests=40)
    r = traffic.offered(m, 3, 50.0, 32064)
    assert len(r) == 40 and {o.due for o in r} == {0.0}


def test_padding_rule():
    assert [traffic.padded(n, 512) for n in (1, 512, 513, 1500)] == [
        512, 512, 1024, 1536]
