"""The Pallas kernels of the serving path, compiled for a described TPU v5e.

Interpret mode cannot show what the TPU compiler refuses: block shapes off
the (8, 128) tiling, more scoped VMEM than a kernel may use, or a kernel
the partitioner would have to split. These tests compile the kernels at
phi3.5-moe's published widths for a v5e that is described, not attached,
and check that each compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file. Where it cannot be described, every test here skips.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import decode_attn
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ops import _divisor_block
from repro.models.layers import KernelConfig

E, D_MODEL, D_FF = 16, 4096, 6400                 # phi3.5-moe experts
B, H, HKV, HEAD_DIM, S = 8, 32, 8, 128, 2048       # decode: 8 slots, 2048 cap
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels_in(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("cap", [8, 256])
def test_moe_gmm_compiles_at_phi35_widths(one_chip, cap):
    kc = KernelConfig()
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = functools.partial(moe_gmm, block_c=_divisor_block(cap, kc.block_c, 8),
                           block_f=_divisor_block(D_FF, kc.block_f, 128))
    compiled = jax.jit(
        lambda x, wg, wu, wd, gs: fn(x, wg, wu, wd, group_sizes=gs)).lower(
        sds((E, cap, D_MODEL), BF16), sds((E, D_MODEL, D_FF), BF16),
        sds((E, D_MODEL, D_FF), BF16), sds((E, D_FF, D_MODEL), BF16),
        sds((E,), jnp.int32)).compile()
    assert _kernels_in(compiled) == 1


@pytest.mark.parametrize("cap", [8, 256])
def test_moe_gmm_compiles_on_layer_stack(one_chip, cap):
    """A serving scan hands the kernel the whole (L, E, ...) expert stack
    and a traced layer index: one kernel, no slice of the stack."""
    kc = KernelConfig()
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = functools.partial(moe_gmm, block_c=_divisor_block(cap, kc.block_c, 8),
                           block_f=_divisor_block(D_FF, kc.block_f, 128))
    compiled = jax.jit(
        lambda x, wg, wu, wd, gs, i: fn(x, wg, wu, wd, layer=i,
                                        group_sizes=gs)).lower(
        sds((E, cap, D_MODEL), BF16), sds((4, E, D_MODEL, D_FF), BF16),
        sds((4, E, D_MODEL, D_FF), BF16), sds((4, E, D_FF, D_MODEL), BF16),
        sds((E,), jnp.int32), sds((), jnp.int32)).compile()
    assert _kernels_in(compiled) == 1
    assert f"bf16[{E},{D_MODEL},{D_FF}]" not in compiled.as_text()


def test_kernel_decode_step_holds_no_expert_copy(one_chip, monkeypatch):
    """The kernel-path decode program of a four-layer phi3.5-moe cut at
    published widths: ``moe_gmm`` reads each layer's experts from the
    stacked parameters, so no one-layer expert array (16 x 4096 x 6400,
    839 MB in bf16) exists anywhere in the compiled program."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import Model

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # compile, not run
    cfg = dataclasses.replace(get_config("phi3.5-moe-42b-a6.6b"), n_layers=4)
    model = Model(cfg).with_kernels(KernelConfig())

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(
        lambda: model.init_cache(B, S, per_slot_len=True)))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, sds((B, 1), jnp.int32), cache, sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert _kernels_in(compiled) == 2                  # moe_gmm, decode_attn
    for one_layer in (f"bf16[{E},{D_MODEL},{D_FF}]",
                      f"bf16[{E},{D_FF},{D_MODEL}]"):
        assert one_layer not in text


def test_decode_attn_compiles_at_phi35_widths(one_chip):
    block_s = _divisor_block(S, KernelConfig().block_s, 8)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(functools.partial(decode_attn, block_s=block_s)).lower(
        sds((B, H, HEAD_DIM), BF16), sds((B, S, HKV, HEAD_DIM), BF16),
        sds((B, S, HKV, HEAD_DIM), BF16), sds((B,), jnp.int32)).compile()
    assert _kernels_in(compiled) == 1


def test_decode_attn_compiles_on_ep_mesh(topo, monkeypatch):
    """On the four-chip EP mesh the kernel runs per head shard inside
    ``shard_map``: the partitioner cannot split a Pallas call itself."""
    from repro.kernels import ops
    from repro.launch.mesh import make_ep_mesh
    from repro.models.attention import _decode_attn_kernel
    from repro.models.layers import ParallelContext

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # compile, not run
    mesh = make_ep_mesh(4, devices=topo.devices)
    pc = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model",
                         ep_axes=("model",), kernels=KernelConfig())
    rep = NamedSharding(mesh, P())
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=rep)
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            lambda q, k, v, n: _decode_attn_kernel(q, k, v, n, pc)).lower(
            sds((B, H, HEAD_DIM), BF16), sds((B, S, HKV, HEAD_DIM), BF16),
            sds((B, S, HKV, HEAD_DIM), BF16), sds((B,), jnp.int32)).compile()
    assert _kernels_in(compiled) == 1
