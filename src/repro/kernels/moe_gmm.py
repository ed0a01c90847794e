"""Pallas TPU kernel: grouped expert FFN (the MoE compute hot-spot).

Computes, per expert e over its capacity bucket:

    y[e] = (act(x[e] @ w_gate[e]) * (x[e] @ w_up[e])) @ w_down[e]

in one fused kernel — the (E, C, d) dispatch buffer produced by the
sort-based ragged dispatch (or the all-to-all) is consumed directly, so the
gate/up/down matmuls and the activation never round-trip through HBM between
them.

TPU mapping: grid (E, C/bc, F/bf) with the f-axis innermost as a reduction —
each (e, c) output block accumulates partial ``h_blk @ w_down_blk`` products
across f-steps in a float32 VMEM scratch accumulator, flushing to the output
on the last step. Block shapes keep the working set in VMEM: x (bc,d),
w_gate/w_up (d,bf), w_down (bf,d) and the output block, each double
buffered, plus the (bc,d) f32 accumulator — about 12 MiB at bc=bf=128,
d=4096 in bf16. Every matmul dim is a multiple of 128 for the MXU.

**Stacked weights** (``layer``): the weights may carry a leading layer
axis, (L, E, d, f) / (L, E, f, d), as a model's layer stack stores them.
The layer index and the (E,) group sizes are scalar-prefetch operands
(``PrefetchScalarGridSpec``), so they sit in SMEM before the grid runs;
the weight index maps return ``(layer, e, 0, f)`` / ``(layer, e, f, 0)``
and the pipeline DMAs each (d, bf) / (bf, d) block of that layer straight
from the stack in HBM. A layer scan hands the kernel the whole stack and
its index, so no per-layer copy of the experts' weights exists (XLA
cannot fuse a slice into a custom call's operand, so slicing first would
materialize one). Weights of one layer are the stack with L = 1.

**Ragged groups** (``group_sizes``): the serving dispatch path routes only a
handful of real tokens per step, so most capacity rows are zero padding.
Every (e, c)-block whose row range starts at or beyond its group's fill
level skips all three matmuls — the MegaBlocks-style dropless-group idea
at block granularity. Skipped blocks flush the zero accumulator, which
equals the dense result exactly: padding rows are zero and FFN(0) == 0.

Validated against ``ref.moe_ffn_ref`` in interpret mode, compiled for a
described TPU v5e at phi3.5-moe widths (E=16, d=4096, f=6400; capacity 8
and 256: ``tests/test_tpu_compile.py``), and run on a v5e chip inside the
phi3.5-moe serving path (``chip_smoke.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def align_capacity(cap: int, block_c: int) -> int:
    """Smallest padded capacity the kernel grid can tile with ``block_c``.

    ``capacity()`` rounds to a multiple of 8, which need not divide into
    ``block_c`` blocks (e.g. cap=136 with block_c=128). A bucket that fits in
    one block is its own (shrunk) block; anything larger is padded up to a
    whole number of blocks. The extra rows are zero padding that the ragged
    ``group_sizes`` path skips entirely.
    """
    if cap <= block_c:
        return cap
    return -(-cap // block_c) * block_c


def _kernel(layer_ref, gs_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref,
            *, act: str, n_f: int, block_c: int):
    del layer_ref                    # read by the weight index maps only
    f_idx = pl.program_id(2)

    @pl.when(f_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block (e, c) holds bucket rows [c*bc, (c+1)*bc); with fewer than
    # c*bc + 1 routed rows the whole block is zero padding — skip the MXU
    # work. (Partially-filled blocks still run; their pad rows are zero
    # inputs, and FFN(0) == 0 keeps the output exact.)
    live = gs_ref[pl.program_id(0)] > pl.program_id(1) * block_c

    @pl.when(live)
    def _compute():
        x = x_ref[0]                               # (bc, d)
        hg = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        hu = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        act_fn = jax.nn.gelu if act == "geglu" else jax.nn.silu
        h = (act_fn(hg) * hu).astype(x.dtype)      # (bc, bf)
        acc_ref[...] += jnp.dot(h, wd_ref[0, 0],
                                preferred_element_type=jnp.float32)

    @pl.when(f_idx == n_f - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def moe_gmm(x, w_gate, w_up, w_down, *, layer=None, group_sizes=None,
            act: str = "swiglu", block_c: int = 128, block_f: int = 128,
            interpret: bool = False):
    """Fused grouped expert FFN.

    x: (E, C, d); w_gate/w_up: (L, E, d, f); w_down: (L, E, f, d) →
    (E, C, d), with the experts of layer ``layer`` (a traced int32
    scalar). Weights of one layer, (E, d, f) / (E, f, d) with
    ``layer=None``, are the stack with L = 1.
    C and f must be divisible by the block sizes (``align_capacity`` gives a
    compliant C; ``ops.moe_ffn`` derives a legal f block).

    ``group_sizes``: optional (E,) int32 count of real rows per bucket —
    blocks past a group's fill level are skipped (flushed as zeros). None
    runs every block (the dense all-to-all layout).
    """
    if w_gate.ndim == 3:
        if layer is not None:
            raise ValueError("layer given for weights of one layer")
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    if c % bc or f % bf:
        raise ValueError(f"C={c} / F={f} not divisible by blocks {bc}/{bf}")
    if group_sizes is None:
        group_sizes = jnp.full((e,), c, jnp.int32)
    group_sizes = group_sizes.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    n_f = f // bf
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # layer, group_sizes
        grid=(e, c // bc, n_f),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e_, c_, f_, l, g: (e_, c_, 0)),
            pl.BlockSpec((1, 1, d, bf),
                         lambda e_, c_, f_, l, g: (l[0], e_, 0, f_)),
            pl.BlockSpec((1, 1, d, bf),
                         lambda e_, c_, f_, l, g: (l[0], e_, 0, f_)),
            pl.BlockSpec((1, 1, bf, d),
                         lambda e_, c_, f_, l, g: (l[0], e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d),
                               lambda e_, c_, f_, l, g: (e_, c_, 0)),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, act=act, n_f=n_f, block_c=bc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(layer, group_sizes, x, w_gate, w_up, w_down)
