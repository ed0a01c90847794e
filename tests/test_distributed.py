"""Distributed-correctness tests, run in subprocesses with 8 host devices.

The main pytest process must keep seeing ONE device (smoke tests/benches),
so anything needing a mesh runs via ``python -c`` with XLA_FLAGS set in the
child environment only.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_ep_dispatch_matches_dense():
    """EP shard_map all_to_all dispatch ≡ the dense reference dispatch."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.launch.mesh import make_mesh
    from repro.models.moe import moe_apply_dense, moe_apply_ep, init_moe
    from repro.models.layers import ParallelContext
    from repro.configs.base import MoEConfig

    mesh = make_mesh((2, 4), ("data", "model"))
    moe = MoEConfig(n_experts=8, top_k=2, d_ff=64, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = init_moe(key, 32, moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    pc = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model",
                         ep_axes=("data", "model"),
                         token_axes=("data", "model"), moe_impl="ep")
    y_dense, aux_d = moe_apply_dense(p, x, moe, "swiglu")
    with jax.set_mesh(mesh):
        y_ep, aux_e = moe_apply_ep(p, x, moe, "swiglu", pc)
    # capacity_factor is large enough that no tokens drop in either path;
    # EP capacity is per-source-device so bucket POSITIONS differ, but the
    # combined output must match.
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    # Aux loss: EP computes the GShard per-group (per-device) estimator
    # E_group[f·P], dense the global one — bilinear, so they differ by
    # sampling noise. Both must be finite and of the same magnitude.
    assert np.isfinite(float(aux_e)) and np.isfinite(float(aux_d))
    assert 0.5 < float(aux_e) / float(aux_d) < 2.0, (aux_e, aux_d)
    print("EP OK")
    """)


def test_aurora_rounds_match_all_to_all():
    """The scheduled ppermute exchange ≡ monolithic lax.all_to_all."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.distributed.alltoall import ep_all_to_all, round_robin_rounds

    mesh = make_mesh((8,), ("ep",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * 8, 4, 16))

    def f(rounds):
        return jax.shard_map(
            lambda b: ep_all_to_all(b, ("ep",), rounds),
            mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
            check_vma=False)(x)

    base = f(None)
    sched = f(round_robin_rounds(8))
    np.testing.assert_allclose(np.asarray(sched), np.asarray(base))
    print("ROUNDS OK")
    """)


def test_aurora_schedule_rounds_cover_all_pairs():
    """BvN-derived rounds (from a real schedule) also reproduce all_to_all."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core import synthetic_trace, aurora_schedule
    from repro.distributed.alltoall import (ep_all_to_all,
                                            aurora_rounds_from_schedule)

    n = 8
    trace = synthetic_trace("t", n_experts=n, n_layers=1, seed=3)
    sched = aurora_schedule(trace.layer(0))
    rounds = aurora_rounds_from_schedule(sched, n)
    # Coverage: every ordered off-diagonal pair appears exactly once.
    seen = np.zeros((n, n), int)
    for dst in rounds:
        for i, j in enumerate(dst):
            if j >= 0:
                seen[i, j] += 1
    off = ~np.eye(n, dtype=bool)
    assert (seen[off] == 1).all(), seen

    mesh = make_mesh((8,), ("ep",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * 8, 4, 16))
    def f(rounds):
        return jax.shard_map(
            lambda b: ep_all_to_all(b, ("ep",), rounds),
            mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
            check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(f(rounds)), np.asarray(f(None)))
    print("BVN ROUNDS OK")
    """)


def test_full_moe_layer_aurora_schedule_matches_dense():
    """End-to-end: a full EP MoE layer running the PLANNED Aurora ppermute
    schedule (BvN rounds from historical traffic) equals the dense
    reference — the schedule changes when bytes move, never what arrives."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs.base import MoEConfig
    from repro.core import aurora_schedule, synthetic_trace
    from repro.distributed import aurora_rounds_from_schedule
    from repro.models.layers import ParallelContext
    from repro.models.moe import init_moe, moe_apply_dense, moe_apply_ep

    n = 8
    mesh = make_mesh((n,), ("model",))
    moe = MoEConfig(n_experts=n, top_k=2, d_ff=64, capacity_factor=8.0)
    p = init_moe(jax.random.PRNGKey(0), 32, moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    sched = aurora_schedule(synthetic_trace("h", n_experts=n, n_layers=1,
                                            seed=7).layer(0))
    rounds = aurora_rounds_from_schedule(sched, n)
    pc = ParallelContext(mesh=mesh, data_axes=(), model_axis="model",
                         ep_axes=("model",), token_axes=("model",),
                         moe_impl="aurora", aurora_rounds=rounds)
    y_ref, _ = moe_apply_dense(p, x, moe, "swiglu")
    with jax.set_mesh(mesh):
        y_aur, _ = moe_apply_ep(p, x, moe, "swiglu", pc)
    np.testing.assert_allclose(np.asarray(y_aur), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    print("AURORA LAYER OK")
    """)


def test_moe_smoke_on_mesh_multipod_axes():
    """phi3.5-style reduced MoE model trains a step on a (pod,data,model)
    mesh with EP over model only."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import get_config
    from repro.models import Model, cross_entropy
    from repro.sharding import make_pc
    import dataclasses

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    # 4 experts over a model axis of 2 → EP=2, experts_per_device=2.
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pc = make_pc(cfg, mesh, moe_impl="ep")
    # 4 experts on data×model = 4 → the widest EP axis is chosen; the pod
    # axis must never join it.
    assert pc.ep_axes == ("data", "model"), pc.ep_axes
    assert "pod" not in pc.ep_axes and pc.token_axes[0] == "pod"
    model = Model(cfg, pc)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)

    with jax.set_mesh(mesh):
        def loss_fn(p):
            logits, aux = model.train_logits(p, {"tokens": tokens},
                                             remat=False)
            return cross_entropy(logits, tokens, cfg.vocab) + 0.01 * aux
        loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
    print("MESH MOE OK", float(loss))
    """)
