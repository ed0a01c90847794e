"""Round semantics of the ppermute lowering (host-side + 1-device mesh).

The exchange in ``repro.distributed.alltoall`` is only correct when the
round sequence is a *cover*: every ordered off-diagonal (src, dst) pair
appears in exactly one round, and every round is a partial permutation.
These properties are cheap to check host-side for both round constructors;
the mesh-collective equivalence runs in ``tests/test_distributed.py`` /
``tests/test_distributed_serving.py`` (8 host devices, subprocess).
"""

import numpy as np
import pytest
from _propcheck import given, settings, st  # hypothesis if installed

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aurora_schedule, synthetic_trace
from repro.core.schedule import CommSchedule, Slot, validate_permutation_slots
from repro.launch.mesh import make_mesh
from repro.distributed import (aurora_rounds_from_schedule, ep_all_to_all,
                               round_robin_rounds)


def _coverage(rounds, n):
    """Assert every round is a partial permutation; return the (n, n) count
    of how often each ordered pair is exchanged."""
    seen = np.zeros((n, n), int)
    for dst in rounds:
        assert len(dst) == n
        real = [j for j in dst if j >= 0]
        assert len(real) == len(set(real)), "two senders hit one receiver"
        for i, j in enumerate(dst):
            if j >= 0:
                assert i != j, "self-send crossed the network"
                seen[i, j] += 1
    return seen


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_round_robin_rounds_cover_each_pair_once(n):
    seen = _coverage(round_robin_rounds(n), n)
    off = ~np.eye(n, dtype=bool)
    assert (seen[off] == 1).all()
    assert (np.diag(seen) == 0).all()


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 10), st.integers(0, 10_000), st.floats(0.0, 1.0))
def test_bvn_rounds_cover_each_pair_once(n, seed, density):
    """Round-trip property: schedule → rounds covers every ordered pair
    exactly once, whatever the traffic looked like (sparse rows, zero rows,
    pairs absent from the schedule get cleanup rounds)."""
    rng = np.random.default_rng(seed)
    d = rng.random((n, n)) * (rng.random((n, n)) < density)
    np.fill_diagonal(d, 0.0)
    rounds = aurora_rounds_from_schedule(aurora_schedule(d), n)
    seen = _coverage(rounds, n)
    off = ~np.eye(n, dtype=bool)
    assert (seen[off] == 1).all(), seen


def test_degenerate_schedules():
    """Single device and zero-traffic rows are explicit, not accidental."""
    # n == 1: self-traffic never crosses the network — no rounds at all.
    assert aurora_rounds_from_schedule(aurora_schedule(np.zeros((1, 1))), 1) \
        == ()
    assert round_robin_rounds(1) == ()
    # All-zero traffic: empty schedule, but the lowering still needs a full
    # cover (traffic drift §8 Q4) — cleanup rounds provide it.
    rounds = aurora_rounds_from_schedule(aurora_schedule(np.zeros((4, 4))), 4)
    assert (_coverage(rounds, 4)[~np.eye(4, dtype=bool)] == 1).all()
    # One silent device (zero row AND column) still gets cleanup coverage.
    d = np.zeros((4, 4))
    d[0, 1] = d[1, 0] = 3.0
    rounds = aurora_rounds_from_schedule(aurora_schedule(d), 4)
    assert (_coverage(rounds, 4)[~np.eye(4, dtype=bool)] == 1).all()


def test_non_permutation_slots_raise():
    """Malformed slots fail loudly instead of silently misrouting buckets."""
    def sched(dst):
        return CommSchedule(slots=(Slot(dst=tuple(dst), duration=1.0),),
                            b_max=1.0)

    with pytest.raises(ValueError, match="two senders"):
        aurora_rounds_from_schedule(sched([1, -1, 1]), 3)
    with pytest.raises(ValueError, match="self-send"):
        aurora_rounds_from_schedule(sched([0, 2, 1]), 3)
    with pytest.raises(ValueError, match="out of range"):
        aurora_rounds_from_schedule(sched([3, -1, -1]), 3)
    with pytest.raises(ValueError, match="entries for"):
        aurora_rounds_from_schedule(sched([1, 0]), 3)
    with pytest.raises(ValueError, match="positive device count"):
        validate_permutation_slots((), 0)
    # A valid schedule passes through the validator untouched.
    validate_permutation_slots(sched([1, 0, -1]).slots, 3)


def test_literal_rounds_demand_a_full_cover():
    """Rounds installed verbatim on an engine (``swap_rounds`` / ctor
    ``rounds=``) must cover every ordered pair exactly once — a truncated
    cover would silently drop token buckets in flight."""
    from repro.distributed.alltoall import validate_rounds_cover

    good = round_robin_rounds(4)
    assert validate_rounds_cover(good, 4) == good
    assert validate_rounds_cover((), 1) == ()
    with pytest.raises(ValueError, match="never exchanged"):
        validate_rounds_cover(good[:-1], 4)            # truncated cover
    with pytest.raises(ValueError, match="more than once"):
        validate_rounds_cover(good + good[-1:], 4)     # duplicate round
    with pytest.raises(ValueError, match="two senders"):
        validate_rounds_cover(((1, -1, 1),), 3)
    with pytest.raises(ValueError, match="self-send"):
        validate_rounds_cover(((0, -1, -1),), 3)
    with pytest.raises(ValueError, match="out of range"):
        validate_rounds_cover(((9, -1, -1),), 3)
    with pytest.raises(ValueError, match="entries for"):
        validate_rounds_cover(((1, 0),), 3)


def test_schedule_traffic_roundtrip():
    """``CommSchedule.traffic`` recovers what the slots move (the inverse
    view the distributed round refresh consumes)."""
    rng = np.random.default_rng(3)
    d = rng.random((5, 5)) * 10
    np.fill_diagonal(d, 0.0)
    sent = aurora_schedule(d).traffic()
    assert sent.shape == (5, 5)
    # Conservation (same property the schedule tests assert): everything
    # real moves, nothing is invented on empty pairs.
    assert (sent + 1e-6 >= d).all()
    assert (sent[d <= 1e-12] <= 1e-8).all()
    assert CommSchedule(slots=(), b_max=0.0).traffic().shape == (0, 0)
    assert CommSchedule(slots=(), b_max=0.0).traffic(3).shape == (3, 3)


def test_ep_all_to_all_identity_on_one_device_mesh():
    """A 1-device mesh's exchange is the identity for every lowering: the
    monolithic all_to_all, an empty round schedule, and the BvN-derived
    rounds of a 1-device schedule (== empty)."""
    mesh = make_mesh((1,), ("ep",))
    x = jnp.arange(24, dtype=jnp.float32).reshape(1, 6, 4)
    rounds_1 = aurora_rounds_from_schedule(
        aurora_schedule(np.zeros((1, 1))), 1)

    for rounds in (None, (), rounds_1):
        y = jax.jit(jax.shard_map(
            lambda b, rounds=rounds: ep_all_to_all(b, ("ep",), rounds),
            mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
            check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_trace_rounds_roundtrip_through_device_aggregation():
    """Expert-granularity traces aggregate onto fewer devices and still
    yield a full contention-free cover (the serving engines' path)."""
    from repro.serving import device_traffic, rounds_from_trace

    trace = synthetic_trace("t", n_experts=16, n_layers=3, seed=11)
    for n_dev in (2, 4, 8, 16):
        rounds = rounds_from_trace(trace, n_dev)
        seen = _coverage(rounds, n_dev)
        off = ~np.eye(n_dev, dtype=bool)
        assert (seen[off] == 1).all()
    agg = device_traffic(trace.layer(0), 4)
    assert agg.shape == (4, 4)
    assert np.trace(agg) == 0.0
    with pytest.raises(ValueError, match="do not shard"):
        device_traffic(trace.layer(0), 5)
