"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Kernels execute with ``interpret=True`` (CPU container; TPU is the target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import decode_attn
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels import ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("e,c,d,f", [
    (2, 128, 64, 128),
    (4, 256, 128, 256),
    (1, 128, 256, 384),
    (3, 384, 96, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["swiglu", "geglu"])
def test_moe_gmm_matches_ref(e, c, d, f, dtype, act):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    wg = jax.random.normal(ks[1], (e, d, f), dtype) * d ** -0.5
    wu = jax.random.normal(ks[2], (e, d, f), dtype) * d ** -0.5
    wd = jax.random.normal(ks[3], (e, f, d), dtype) * f ** -0.5
    got = moe_gmm(x, wg, wu, wd, act=act, interpret=True)
    want = ref.moe_ffn_ref(x, wg, wu, wd, act)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("sizes", [
    (0, 0),              # every block dead
    (128, 0),            # one full group, one empty
    (37, 200),           # partial blocks (ragged fill levels)
])
def test_moe_gmm_group_sizes_skip_matches_dense(sizes):
    """Ragged groups: with zero-padded buckets, skipping empty expert blocks
    must be invisible — the output equals the dense (no group_sizes) run and
    the masked reference, because pad rows are zero and FFN(0) == 0."""
    e, c, d, f = 2, 256, 64, 128
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    live = jnp.arange(c)[None, :] < gs[:, None]
    x = jnp.where(live[..., None], x, 0.0)              # zero-padded buckets
    wg = jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
    wu = jax.random.normal(ks[2], (e, d, f)) * d ** -0.5
    wd = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    got = moe_gmm(x, wg, wu, wd, group_sizes=gs, block_c=64, interpret=True)
    dense = moe_gmm(x, wg, wu, wd, block_c=64, interpret=True)
    want = ref.moe_ffn_ref(x, wg, wu, wd, "swiglu", group_sizes=gs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block", [64, 128])
def test_moe_gmm_block_sweep(block):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    e, c, d, f = 2, 256, 128, 256
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32)
    wg = jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
    wu = jax.random.normal(ks[2], (e, d, f)) * d ** -0.5
    wd = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    got = moe_gmm(x, wg, wu, wd, block_c=block, block_f=block,
                  interpret=True)
    want = ref.moe_ffn_ref(x, wg, wu, wd, "swiglu")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [0, 1, 2, None])
def test_moe_gmm_reads_layer_of_stack(layer):
    """Weights stacked by layer (L = 3, a layer scan's stack): the kernel
    reads layer ``layer``'s blocks in place and equals the kernel and the
    reference on that layer's slice, over ragged groups (one empty, one
    partial, one full). ``layer=None`` passes one layer's 3-D weights, as
    callers outside a layer scan do."""
    n_layers, e, c, d, f = 3, 3, 256, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    gs = jnp.asarray([0, 37, 256], jnp.int32)
    live = jnp.arange(c)[None, :] < gs[:, None]
    x = jnp.where(live[..., None],
                  jax.random.normal(ks[0], (e, c, d), jnp.float32), 0.0)
    wg = jax.random.normal(ks[1], (n_layers, e, d, f)) * d ** -0.5
    wu = jax.random.normal(ks[2], (n_layers, e, d, f)) * d ** -0.5
    wd = jax.random.normal(ks[3], (n_layers, e, f, d)) * f ** -0.5
    if layer is None:
        stack, one = (wg[1], wu[1], wd[1]), (wg[1], wu[1], wd[1])
    else:
        stack, one = (wg, wu, wd), (wg[layer], wu[layer], wd[layer])
        layer = jnp.int32(layer)                     # traced, as in a scan
    got = moe_gmm(x, *stack, layer=layer, group_sizes=gs, block_c=64,
                  interpret=True)
    sliced = moe_gmm(x, *one, group_sizes=gs, block_c=64, interpret=True)
    want = ref.moe_ffn_ref(x, *one, "swiglu", group_sizes=gs)
    np.testing.assert_array_equal(got, sliced)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        ref.moe_ffn_ref(x, *stack, "swiglu", group_sizes=gs, layer=layer),
        want, rtol=0, atol=0)
    assert not np.asarray(got[0]).any()              # the empty group


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 8, 8, 512, 64),      # MHA
    (2, 8, 2, 1024, 64),     # GQA 4:1
    (1, 16, 4, 2048, 128),   # GQA 4:1, bigger head
    (3, 4, 1, 512, 128),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_matches_ref(b, h, hkv, s, d, dtype):
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    valid = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
    got = decode_attn(q, k, v, valid, block_s=256, interpret=True)
    want = ref.decode_attn_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attn_partial_fill_blocks():
    """valid_len smaller than one block must zero out later blocks entirely."""
    b, h, hkv, s, d = 1, 4, 4, 1024, 64
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    valid = jnp.array([3], jnp.int32)
    got = decode_attn(q, k, v, valid, block_s=256, interpret=True)
    want = ref.decode_attn_ref(q, k, v, valid)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_decode_attn_matches_model_attention():
    """Cross-check the kernel against the model's attention_core path."""
    from repro.models.layers import attention_core
    b, h, hkv, s, d = 2, 8, 4, 512, 64
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    valid = jnp.full((b,), 400, jnp.int32)
    got = decode_attn(q, k, v, valid, block_s=128, interpret=True)
    # attention_core takes (B, Sq, H, D) and a scalar cache fill level.
    want = attention_core(q[:, None], k, v, causal_offset=None, window=None,
                          valid_len=jnp.int32(400))
    np.testing.assert_allclose(got, want[:, 0], rtol=1e-4, atol=1e-4)
