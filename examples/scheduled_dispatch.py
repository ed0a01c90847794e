"""Aurora-scheduled MoE dispatch on a multi-device mesh (Thm 4.2 runtime).

Runs the SAME expert-parallel MoE layer three ways on 8 CPU host devices:
  1. monolithic ``lax.all_to_all``          (production baseline),
  2. round-robin ppermute rounds           (traffic-blind, contention-free),
  3. Aurora BvN rounds from a planned schedule (traffic-aware ordering),
and verifies all three produce identical outputs — the schedule changes
WHEN bytes move, never WHAT arrives.

Must own the process (device count is locked at jax init):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/scheduled_dispatch.py
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.configs.base import MoEConfig
    from repro.core import aurora_schedule, synthetic_trace
    from repro.distributed import (aurora_rounds_from_schedule,
                                   round_robin_rounds)
    from repro.launch.mesh import make_mesh
    from repro.models.layers import ParallelContext
    from repro.models.moe import init_moe, moe_apply_ep

    n = 8
    mesh = make_mesh((n,), ("model",))
    moe = MoEConfig(n_experts=n, top_k=2, d_ff=128, capacity_factor=4.0)
    params = init_moe(jax.random.PRNGKey(0), 64, moe, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 64))

    # Plan from historical routing statistics (paper §2.4).
    trace = synthetic_trace("hist", n_experts=n, n_layers=1, seed=42)
    sched = aurora_schedule(trace.layer(0))
    rounds = aurora_rounds_from_schedule(sched, n)
    print(f"planned schedule: {sched.n_slots} BvN slots, "
          f"b_max {sched.b_max:.1f} -> {len(rounds)} static ppermute rounds")

    def run(impl, aurora_rounds=None):
        pc = ParallelContext(mesh=mesh, data_axes=(), model_axis="model",
                             ep_axes=("model",), token_axes=("model",),
                             moe_impl=impl, aurora_rounds=aurora_rounds)
        with jax.set_mesh(mesh):
            y, aux = moe_apply_ep(params, x, moe, "swiglu", pc)
        return np.asarray(y)

    y_base = run("ep")
    y_rr = run("aurora", round_robin_rounds(n))
    y_aurora = run("aurora", rounds)
    np.testing.assert_allclose(y_rr, y_base, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_aurora, y_base, rtol=1e-5, atol=1e-5)
    print("all three dispatch implementations agree "
          f"(max |Δ| = {np.abs(y_aurora - y_base).max():.2e})")
    print("on TPU the Aurora rounds avoid receiver contention for the "
          "planned traffic — see EXPERIMENTS.md §Perf")


if __name__ == "__main__":
    main()
