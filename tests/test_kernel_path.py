"""Kernelized serving hot path: sort-based ragged dispatch + engine wiring.

The kernel tier runs twice in CI: once with the pure-jnp fallback (fast,
every matrix leg) and once with ``REPRO_KERNEL_TIER=interpret`` exported,
which forces the engine-level tests through the Pallas kernel bodies in
interpret mode (the closest a CPU container gets to the TPU path).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.configs import get_config
from repro.models import KernelConfig, Model, NO_PARALLEL, ParallelContext
from repro.models.moe import (capacity, dispatch_indices, init_moe,
                              moe_apply, routed_counts, sort_dispatch)
from repro.serving import (ColocatedContinuousEngine, ContinuousEngine,
                           EngineConfig, MultiTenantContinuousEngine,
                           OnlineReplanner, Request, TrafficMonitor)

INTERPRET_TIER = os.environ.get("REPRO_KERNEL_TIER") == "interpret"


def _engine_kernels():
    """``EngineConfig.kernels`` value for engine tests: plain fallback
    normally, Pallas interpret mode when the interpret tier is selected."""
    return KernelConfig(interpret=True) if INTERPRET_TIER else True


def _kernel_pc(**kw):
    return ParallelContext(moe_impl="kernel", kernels=KernelConfig(**kw))


def _model(arch="phi3.5-moe-42b-a6.6b", seed=0):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _requests(n=5, seed=0, max_new=5, plen=6, vocab=500):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, vocab, plen)),
                    max_new_tokens=max_new, arrival=float(i))
            for i in range(n)]


# -- sort-based dispatch ----------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(1, 4), st.integers(1, 16),
       st.integers(0, 10_000))
def test_sort_dispatch_matches_one_hot(t, k, e, seed):
    """Sort-based dispatch is ``dispatch_indices`` bit for bit: same bucket
    slot, same kept/dropped set under capacity pressure (GShard token-order
    tie-breaking), and group sizes equal to the offered-traffic histogram."""
    k = min(k, e)
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, e, (t, k)), jnp.int32)
    # Deliberately tight capacity so overflow actually happens.
    cap = int(rng.integers(1, max(2, t // 2 + 1)))
    slot_ref, keep_ref = dispatch_indices(idx, e, cap)
    _, sizes, slot, keep = sort_dispatch(idx, e, cap)
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(slot_ref))
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(keep_ref))
    hist = np.bincount(np.asarray(idx).reshape(-1), minlength=e)
    np.testing.assert_array_equal(np.asarray(sizes), hist)


def test_routed_counts_matches_one_hot():
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, 8, (12, 2)), jnp.int32)
    want = jax.nn.one_hot(idx, 8, dtype=jnp.float32).sum(axis=1)
    np.testing.assert_allclose(np.asarray(routed_counts(idx, 8)),
                               np.asarray(want))


# -- kernel MoE layer vs dense reference ------------------------------------

@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b",
                                  "deepseek-v3-671b"])
@pytest.mark.parametrize("t", [2, 4, 33])
def test_moe_apply_kernel_matches_dense(arch, t):
    """Same routing, same drops, same combine: kernel-path outputs match the
    dense reference to fp32 tolerance for both router families (softmax and
    sigmoid+shared-expert), at decode- and prefill-sized token counts."""
    cfg = get_config(arch).reduced()
    moe = cfg.moe
    p = init_moe(jax.random.PRNGKey(0), cfg.d_model, moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.d_model),
                          jnp.float32)
    y_d, aux_d, c_d = moe_apply(p, x, moe, cfg.act, NO_PARALLEL,
                                return_counts=True)
    y_k, aux_k, c_k = moe_apply(p, x, moe, cfg.act, _kernel_pc(),
                                return_counts=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_d),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_k), float(aux_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_d))


@pytest.mark.parametrize("block_c", [16, 8, 128])
def test_moe_apply_kernel_interpret_capacity_alignment(block_c):
    """Regression: ``capacity(multiple=8)`` need not divide into the kernel's
    ``block_c`` grid — the kernel path pads the bucket to ``align_capacity``
    and must stay exact through the Pallas body (interpret mode) for block
    sizes that divide, shrink to, and overshoot the capacity."""
    from repro.kernels.moe_gmm import align_capacity

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    moe = cfg.moe
    p = init_moe(jax.random.PRNGKey(0), cfg.d_model, moe, jnp.float32)
    t = 32                                  # capacity() -> 24: 8 divides
    #                                         it, 16 pads it to 32, 128
    #                                         shrinks to it
    cap = capacity(t, moe.top_k, moe.n_experts, moe.capacity_factor)
    assert align_capacity(cap, block_c) % min(block_c, cap) == 0
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.d_model),
                          jnp.float32)
    y_d, _ = moe_apply(p, x, moe, cfg.act)
    y_k, _ = moe_apply(p, x, moe, cfg.act,
                       _kernel_pc(interpret=True, block_c=block_c))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_d),
                               rtol=2e-5, atol=2e-5)


def test_moe_apply_counts_flow_on_every_path():
    """``return_counts`` is available on every dispatch path: dense and
    kernel locally (here), EP/aurora in-collective — routing runs inside the
    shard_map all-to-all, so the counts are psum-replicated out of it
    (mesh-backed equality with the dense histogram is asserted in
    ``tests/test_distributed_serving.py``)."""
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg.d_model, cfg.moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.d_model))
    _, _, c_dense = moe_apply(p, x, cfg.moe, cfg.act, return_counts=True)
    _, _, c_kernel = moe_apply(p, x, cfg.moe, cfg.act, _kernel_pc(),
                               return_counts=True)
    assert c_dense.shape == (4, cfg.moe.n_experts)
    np.testing.assert_array_equal(np.asarray(c_kernel), np.asarray(c_dense))


# -- decode_attn_auto -------------------------------------------------------

def test_decode_attn_auto_broadcasts_and_tiles():
    from repro.kernels import ref
    from repro.kernels.ops import decode_attn_auto

    b, h, hkv, s, d = 2, 4, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    # scalar fill level broadcasts to every row
    got = decode_attn_auto(q, k, v, jnp.int32(7))
    want = ref.decode_attn_ref(q, k, v, jnp.full((b,), 7, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # interpret mode: S=24 does not divide block_s=16 — a legal block is
    # derived (the largest 8-aligned divisor) instead of tripping the grid
    # check
    got_i = decode_attn_auto(q, k, v, jnp.full((b,), 7, jnp.int32),
                             block_s=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got_i), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n, block, align, want", [
    (6400, 128, 128, 128),     # phi3.5 expert d_ff: lane-aligned divisor
    (2048, 512, 8, 512),       # decode cache capacity
    (24, 16, 8, 8),            # largest sublane-aligned divisor
    (100, 128, 128, 100),      # whole axis fits one block
    (2880, 128, 128, None),    # no 128-multiple <= 128 divides 2880
])
def test_divisor_block_is_aligned_or_raises(n, block, align, want):
    """A block the TPU compiler would refuse (not a multiple of the tile)
    is never picked silently: the axis fits one block, or an aligned
    divisor exists, or the call raises."""
    from repro.kernels.ops import _divisor_block

    if want is None:
        with pytest.raises(ValueError, match="no block"):
            _divisor_block(n, block, align)
    else:
        assert _divisor_block(n, block, align) == want


# -- engines ----------------------------------------------------------------

def test_continuous_engine_kernel_tokens_and_logits():
    """A full ``ContinuousEngine.serve`` run on the kernel path emits the
    dense path's greedy tokens exactly, and the step-level fp32 logits agree
    to tolerance (checked on a prefill + decode pair with matched caches)."""
    cfg, model, params = _model()
    reqs = lambda: _requests(6, seed=1, max_new=6, vocab=cfg.vocab)
    dense = ContinuousEngine(model, params, 3, 48,
                             config=EngineConfig(prefill_len=8))
    out_d = dense.serve(reqs())
    kern = ContinuousEngine(
        model, params, 3, 48,
        config=EngineConfig(prefill_len=8, kernels=_engine_kernels()))
    out_k = kern.serve(reqs())
    assert [r.out_tokens for r in out_d] == [r.out_tokens for r in out_k]

    mk = model.with_kernels(_engine_kernels())
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, (2, 8)), jnp.int32)
    ld, cd = model.prefill(params, {"tokens": toks}, model.init_cache(2, 16))
    lk, ck = mk.prefill(params, {"tokens": toks}, mk.init_cache(2, 16))
    np.testing.assert_allclose(np.asarray(lk), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    tok = jnp.argmax(ld[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
    ld, _ = model.decode_step(params, tok, cd)
    lk, _ = mk.decode_step(params, tok, ck)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(ld[:, :, :cfg.vocab], -1)),
        np.asarray(jnp.argmax(lk[:, :, :cfg.vocab], -1)))


@pytest.mark.parametrize("replication", [None, (2, 1, 1, 1)])
def test_serving_scan_feeds_expert_stack_token_identity(monkeypatch,
                                                        replication):
    """The serving scan hands ``moe_gmm`` each MoE layer's expert stack and
    the block index, where it used to slice the block's experts first; the
    kernel reads the same bytes, so greedy decoding through the Pallas
    bodies (interpret mode) emits the same tokens either way, with and
    without replicated experts (which widen axis 1 of the stack)."""
    from repro.models import moe as moe_mod
    from repro.models import transformer as tf

    cfg, model, params = _model()
    assert cfg.n_layers > 1                  # the block index picks a layer
    seen = []

    def serve():
        jax.clear_caches()                   # retrace under the patches
        eng = ContinuousEngine(
            model, params, 2, 32,
            config=EngineConfig(prefill_len=8,
                                kernels=KernelConfig(interpret=True)))
        if replication is not None:
            eng.adopt_replication(replication)
        out = eng.serve(_requests(4, seed=3, max_new=5, vocab=cfg.vocab))
        return [r.out_tokens for r in out]

    def spy(*a, layer=None, **k):
        seen.append(layer is not None)
        return moe_mod.moe_apply(*a, layer=layer, **k)

    monkeypatch.setattr(tf, "moe_apply", spy)
    got = serve()
    assert seen and all(seen)                # stacks and indices reached it
    # The scan as it was: every leaf sliced per block, one layer's experts
    # handed to the kernel.
    seen.clear()
    monkeypatch.setattr(tf, "_is_experts_leaf", lambda path: False)
    monkeypatch.setattr(tf, "moe_apply",
                        lambda *a, layer=None, **k: spy(*a, **k))
    want = serve()
    assert seen and not any(seen)
    assert all(want) and got == want


def test_kernel_engine_monitor_counts_match_dense():
    """Routing counts harvested on the kernel path equal the dense path's —
    the re-planner sees the same traffic either way."""
    cfg, model, params = _model()
    reqs = lambda: _requests(4, seed=2, max_new=4, vocab=cfg.vocab)
    mon_d = TrafficMonitor(cfg.moe.n_experts, model.n_moe_layers)
    ContinuousEngine(model, params, 2, 48,
                     config=EngineConfig(prefill_len=8),
                     monitor=mon_d).serve(reqs())
    mon_k = TrafficMonitor(cfg.moe.n_experts, model.n_moe_layers)
    ContinuousEngine(
        model, params, 2, 48,
        config=EngineConfig(prefill_len=8, kernels=_engine_kernels()),
        monitor=mon_k).serve(reqs())
    assert mon_k.observations == mon_d.observations
    np.testing.assert_allclose(mon_k.rates, mon_d.rates, atol=1e-9)


def test_replan_drift_with_kernels():
    """The online re-planning loop runs unchanged on the kernel path: live
    counts flow, plans fire, and re-pairing stays placement-only (token
    streams identical to a never-replanning kernel run)."""
    from repro.core import AuroraPlanner, homogeneous_cluster

    cfg_a, ma, pa = _model(seed=0)
    cfg_b, mb, pb = _model(seed=1)
    planner = AuroraPlanner(homogeneous_cluster(cfg_a.moe.n_experts))
    kern = _engine_kernels()

    mk_a = lambda: _requests(5, seed=3)
    mk_b = lambda: _requests(4, seed=4)
    ref = ColocatedContinuousEngine(ma, mb, pa, pb, 2, 48,
                                    config=EngineConfig(kernels=kern))
    ra0, rb0 = ref.serve(mk_a(), mk_b())

    rp = OnlineReplanner(planner, interval=3, threshold=-1.0, warmup=1)
    eng = ColocatedContinuousEngine(ma, mb, pa, pb, 2, 48, replan=rp,
                                    config=EngineConfig(kernels=kern))
    ra1, rb1 = eng.serve(mk_a(), mk_b())
    assert [r.out_tokens for r in ra0] == [r.out_tokens for r in ra1]
    assert [r.out_tokens for r in rb0] == [r.out_tokens for r in rb1]
    applied = [e for e in eng.replan_events if e.applied]
    assert applied, "forced re-planning never fired on the kernel path"
    assert eng.pair == applied[-1].pair


def test_multi_tenant_kernel_tokens_identical():
    cfg, m0, p0 = _model(seed=0)
    _, m1, p1 = _model(seed=1)
    streams = lambda: [_requests(3, seed=5), _requests(3, seed=6)]
    dense = MultiTenantContinuousEngine([m0, m1], [p0, p1], 2, 48,
                                        config=EngineConfig(prefill_len=8))
    out_d = dense.serve(streams())
    kern = MultiTenantContinuousEngine(
        [m0, m1], [p0, p1], 2, 48,
        config=EngineConfig(prefill_len=8, kernels=_engine_kernels()))
    out_k = kern.serve(streams())
    for sd, sk in zip(out_d, out_k):
        assert [r.out_tokens for r in sd] == [r.out_tokens for r in sk]
