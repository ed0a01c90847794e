"""Distributed serving subsystem tests (8 host devices, subprocess).

Like ``test_distributed.py``, everything needing a mesh runs via
``python -c`` with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in the child environment only, so the main pytest process keeps ONE device.

Covers the three layers of the subsystem:
  1. collective — the round-pipelined dispatch is token-identical to the
     synchronous exchange, and ``return_counts`` works in-collective;
  2. engine — ``DistributedEngine`` serves EP-sharded and ``adopt()`` swaps
     ppermute rounds mid-stream placement-only;
  3. colocated — online re-planning refreshes the rounds, and the refresh
     itself never changes a token.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    env = dict(os.environ)
    # Single-threaded Eigen: a multi-threaded CPU dot splits its
    # reduction by the operand shape, so the same rows computed in a
    # smaller matmul (one pipeline chunk) would round differently and
    # the byte-identity checks below would compare the thread split.
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_cpu_multi_thread_eigen=false")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pipelined_dispatch_matches_sync_and_counts_match_dense():
    """The software pipeline (FFN chunks overlapping in-flight ppermute
    rounds) emits byte-identical outputs to the synchronous exchange, at
    experts_per_device 1 AND > 1, and the in-collective psum'd routing
    counts equal the dense reference's exactly."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import MoEConfig
    from repro.core import aurora_schedule, synthetic_trace
    from repro.launch.mesh import make_mesh
    from repro.distributed import (aurora_rounds_from_schedule,
                                   pipelined_dispatch_combine)
    from repro.models.layers import ParallelContext
    from repro.models.moe import init_moe, moe_apply_dense, moe_apply_ep
    from repro.serving import rounds_from_trace

    mesh = make_mesh((8,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    for e in (8, 16):                       # experts_per_device 1 and 2
        moe = MoEConfig(n_experts=e, top_k=2, d_ff=64, capacity_factor=8.0)
        p = init_moe(jax.random.PRNGKey(e), 32, moe, jnp.float32)
        rounds = rounds_from_trace(
            synthetic_trace("h", n_experts=e, n_layers=1, seed=7), 8)
        pc = ParallelContext(mesh=mesh, data_axes=(), model_axis=None,
                             ep_axes=("model",), token_axes=("model",),
                             moe_impl="aurora", aurora_rounds=rounds)
        pc_pipe = dataclasses.replace(pc, ep_overlap=True)
        y_ref, _, c_ref = jax.jit(lambda x, p=p, moe=moe: moe_apply_dense(
            p, x, moe, "swiglu", return_counts=True))(x)
        with jax.set_mesh(mesh):
            y_sync, _, c_sync = jax.jit(
                lambda x, p=p, moe=moe, pc=pc: moe_apply_ep(
                    p, x, moe, "swiglu", pc, return_counts=True))(x)
            y_pipe, _, c_pipe = jax.jit(
                lambda x, p=p, moe=moe, pc=pc_pipe: moe_apply_ep(
                    p, x, moe, "swiglu", pc, return_counts=True))(x)
        # Token-identity of the pipeline: BYTE-identical to the sync path
        # (same routing, same buckets, same per-row FFN, same combine).
        np.testing.assert_array_equal(np.asarray(y_pipe), np.asarray(y_sync))
        np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        # Counts are integer-valued and frame-identical across all paths.
        np.testing.assert_array_equal(np.asarray(c_sync), np.asarray(c_ref))
        np.testing.assert_array_equal(np.asarray(c_pipe), np.asarray(c_ref))
        # The standalone wrapper (forced pipeline) agrees too.
        with jax.set_mesh(mesh):
            xt = x.reshape(-1, 32)
            y_w, _ = jax.jit(lambda xt, p=p, moe=moe, pc=pc:
                             pipelined_dispatch_combine(
                                 xt, p["router"], p["experts"], moe,
                                 "swiglu", pc))(xt)
        np.testing.assert_array_equal(np.asarray(y_w),
                                      np.asarray(y_pipe.reshape(-1, 32)))
    print("PIPELINE OK")
    """)


def test_distributed_engine_adopt_swaps_rounds_placement_only():
    """``DistributedEngine`` serves a stream EP-sharded (pipelined rounds)
    and a mid-stream ``adopt()`` — fresh BvN rounds from drifted traffic —
    changes the ppermute schedule but not one emitted token."""
    _run("""
    import dataclasses
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core import synthetic_trace
    from repro.launch.mesh import make_ep_mesh
    from repro.models import Model
    from repro.serving import (DistributedEngine, EngineConfig, Request,
                               TrafficMonitor)

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8,
                                     capacity_factor=8.0))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_ep_mesh(8)
    hist = synthetic_trace("hist", n_experts=8, n_layers=2, seed=0)
    drift = synthetic_trace("drift", n_experts=8, n_layers=2, seed=9)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab, 8)) for _ in range(3)]

    def serve(adopt_at, monitor=None):
        eng = DistributedEngine(model, params, batch_slots=2, cache_cap=32,
                                mesh=mesh, rounds=None, plan=hist,
                                overlap=True, monitor=monitor,
                                config=EngineConfig(prefill_len=8))
        r0 = eng.rounds
        for pr in prompts:
            eng.submit(Request(prompt=list(pr), max_new_tokens=6))
        reqs, steps = list(eng.queue), 0
        while eng.step():
            steps += 1
            if steps == adopt_at:
                eng.adopt(drift)
        return eng, r0, [r.out_tokens for r in reqs]

    eng_a, r0, toks_a = serve(adopt_at=None)
    mon = TrafficMonitor(8, eng_a.model.n_moe_layers)
    eng_b, _, toks_b = serve(adopt_at=3, monitor=mon)
    assert eng_b.rounds != r0, "adopt() did not change the round schedule"
    assert all(t for t in toks_a), toks_a
    assert toks_a == toks_b, "rounds swap changed emitted tokens"
    # The monitor harvested in-collective counts from the EP decode path.
    assert mon.observations > 0 and mon.counts.sum() > 0
    print("ADOPT OK", len(r0), "->", len(eng_b.rounds))
    """)


def test_distributed_engine_adopts_replicated_plan_placement_only():
    """A ``plan_replicated(..., total_multiple=n_ep)`` adopted mid-stream
    widens the EP-sharded expert leaves AND swaps the rounds, byte-identical
    token streams; a plan whose physical expert count does not shard over
    the EP axis is refused loudly."""
    _run("""
    import dataclasses
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core import AuroraPlanner, homogeneous_cluster, \\
        trace_from_counts
    from repro.launch.mesh import make_ep_mesh
    from repro.models import Model
    from repro.serving import DistributedEngine, EngineConfig, Request

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8,
                                     capacity_factor=8.0))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_ep_mesh(8)
    planner = AuroraPlanner(homogeneous_cluster(8))
    counts = np.ones((2, 8)); counts[:, 0] = 25.0    # expert 0 runs hot
    skew = trace_from_counts("skew", counts)
    rep_plan = planner.plan_replicated(skew, tolerance=0.05,
                                       total_multiple=8)
    n_phys = sum(len(h) for h in rep_plan.replication)
    assert n_phys % 8 == 0 and n_phys > 8, rep_plan.replication
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab, 8)) for _ in range(3)]

    def serve(adopt_at):
        eng = DistributedEngine(model, params, batch_slots=2, cache_cap=32,
                                mesh=mesh, rounds=None, plan=skew,
                                overlap=True,
                                config=EngineConfig(prefill_len=8))
        for pr in prompts:
            eng.submit(Request(prompt=list(pr), max_new_tokens=6))
        reqs, steps = list(eng.queue), 0
        while eng.step():
            steps += 1
            if steps == adopt_at:
                eng.adopt(rep_plan)
        return eng, [r.out_tokens for r in reqs]

    eng_a, toks_a = serve(adopt_at=None)
    eng_b, toks_b = serve(adopt_at=3)
    assert all(t for t in toks_a), toks_a
    assert toks_a == toks_b, "replication adoption changed emitted tokens"
    spec = eng_b.model.pc.moe_replication
    assert spec is not None and spec.n_phys == n_phys

    # A placement that does not shard over the EP axis is refused.
    bad = planner.plan_replicated(skew, tolerance=0.0, max_total_replicas=1)
    assert sum(len(h) for h in bad.replication) % 8, bad.replication
    try:
        eng_b.adopt(bad)
    except ValueError as e:
        assert "total_multiple=8" in str(e)
    else:
        raise AssertionError("non-divisible replication was adopted")
    print("REPLICATED ADOPT OK", n_phys)
    """)


def test_distributed_colocated_replan_refreshes_rounds_placement_only():
    """The distributed colocated engine closes the full loop on a mesh:
    in-collective counts feed the monitors, the replanner re-pairs from
    live traces, an ADOPTED plan refreshes the ppermute rounds — and the
    refresh is placement-only (identical streams with refresh disabled)."""
    _run("""
    import dataclasses
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core import AuroraPlanner, homogeneous_cluster, synthetic_trace
    from repro.launch.mesh import make_ep_mesh
    from repro.models import Model
    from repro.serving import (DistributedColocatedEngine, EngineConfig,
                               OnlineReplanner, Request, apply_pairing)

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8,
                                     capacity_factor=8.0))
    model_a, model_b = Model(cfg), Model(cfg)
    params_a = model_a.init(jax.random.PRNGKey(0))
    params_b = model_b.init(jax.random.PRNGKey(1))
    planner = AuroraPlanner(homogeneous_cluster(8))
    hist_a = synthetic_trace("ha", n_experts=8, n_layers=2, seed=0)
    hist_b = synthetic_trace("hb", n_experts=8, n_layers=2, seed=1)
    plan0 = planner.plan_colocated(hist_a, hist_b)
    pb = apply_pairing(params_b, list(plan0.pair), cfg)

    rng = np.random.default_rng(0)
    v = cfg.vocab
    streams = [[Request(prompt=list(rng.integers(lo, lo + v // 16, 6)),
                        max_new_tokens=4, arrival=float(i))
                for i in range(4)]
               for lo in (1, v // 2)]

    def serve(refresh):
        rp = OnlineReplanner(planner, interval=3, threshold=-1e9, warmup=1)
        eng = DistributedColocatedEngine(
            model_a, model_b, params_a, pb, batch_slots=2, cache_cap=16,
            mesh=mesh, plan=plan0, overlap=True, refresh_rounds=refresh,
            config=EngineConfig(prefill_len=8), replan=rp,
            monitor_halflife=8.0)
        r0 = eng.rounds
        reqs_a = [Request(prompt=list(r.prompt), max_new_tokens=4,
                          arrival=r.arrival) for r in streams[0]]
        reqs_b = [Request(prompt=list(r.prompt), max_new_tokens=4,
                          arrival=r.arrival) for r in streams[1]]
        eng.serve(reqs_a, reqs_b)
        applied = [e for e in eng.replan_events if e.applied]
        return (eng, r0, applied,
                [r.out_tokens for r in reqs_a],
                [r.out_tokens for r in reqs_b])

    mesh = make_ep_mesh(8)
    eng_r, r0, applied_r, ta_r, tb_r = serve(refresh=True)
    eng_s, _, applied_s, ta_s, tb_s = serve(refresh=False)
    assert len(applied_r) >= 1, "no re-plan applied (threshold=-inf!)"
    assert eng_r.rounds != r0, "adopted re-plan did not refresh the rounds"
    assert eng_s.rounds == r0, "refresh_rounds=False still swapped rounds"
    assert ta_r == ta_s and tb_r == tb_s, \
        "rounds refresh changed emitted tokens (placement-only violated)"
    assert [e.pair for e in applied_r] == [e.pair for e in applied_s], \
        "legs diverged before the refresh could be compared"
    print("COLOCATED REFRESH OK", len(applied_r), "replan(s)")
    """)
