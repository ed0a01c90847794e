"""Random weights from the seed, made by the benchmark on the device in
one jitted call, in the types they are served in, in the parameter layout
the serving engine takes (stacked per layer: ``segments[0][0]``).

The same arrays feed the reference (``reference.py``) after the program's
state is freed: the reference reads them leaf by leaf and takes nothing
that the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def prng_key(seed: int):
    """A key for any whole-number seed (more than 32 bits included). The
    ``rbg`` generator: on a TPU it makes 11 GB of weights in a fraction of
    the time threefry takes, and the same seed gives the same weights."""
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def padded_vocab(vocab: int) -> int:
    """The engine's embedding and head rows: vocab rounded up to 256."""
    return -(-vocab // 256) * 256


def shapes(cfg: dict) -> dict:
    """{leaf path: (shape, dtype, scale)}: each weight is drawn with
    standard deviation ``scale``; None marks a norm weight (served as
    1 + w, drawn with deviation ``NORM_SCALE`` around 0)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], cfg["hidden_size"] // h
    f, e = cfg["intermediate_size"], cfg["num_local_experts"]
    n, vp = cfg["num_hidden_layers"], padded_vocab(cfg["vocab_size"])
    dt = jnp.dtype(cfg["torch_dtype"])
    return {
        "embed": ((vp, d), dt, 0.02),
        "final_norm": ((d,), dt, None),
        "lm_head": ((d, vp), dt, d ** -0.5),
        "ln1": ((n, d), dt, None),
        "ln2": ((n, d), dt, None),
        "attn/wq": ((n, d, h, hd), dt, d ** -0.5),
        "attn/wk": ((n, d, hkv, hd), dt, d ** -0.5),
        "attn/wv": ((n, d, hkv, hd), dt, d ** -0.5),
        "attn/wo": ((n, h, hd, d), dt, (h * hd) ** -0.5),
        # The router is kept in float32, as the engine serves it.
        "moe/router": ((n, d, e), jnp.dtype("float32"), d ** -0.5),
        "moe/experts/w_gate": ((n, e, d, f), dt, d ** -0.5),
        "moe/experts/w_up": ((n, e, d, f), dt, d ** -0.5),
        "moe/experts/w_down": ((n, e, f, d), dt, f ** -0.5),
    }


NORM_SCALE = 0.1


def _nest(flat: dict) -> dict:
    layer: dict = {}
    for path, x in flat.items():
        if path in ("embed", "final_norm", "lm_head"):
            continue
        node = layer
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"], "segments": ((layer,),)}


def generate(cfg: dict, seed: int):
    """The whole parameter tree as one traceable function of the seed."""
    key = prng_key(seed)
    flat = {}
    for i, (path, (shape, dt, scale)) in enumerate(shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        # Uniform with unit variance, drawn in the served type.
        a = 3 ** 0.5 * (NORM_SCALE if scale is None else scale)
        flat[path] = jax.random.uniform(k, shape, dt, -a, a)
    return _nest(flat)


def make(cfg: dict, seed: int, shardings=None):
    """The weights on the device (``shardings``: a pytree of shardings to
    lay them out over a mesh; None = the default device)."""
    return jax.jit(lambda: generate(cfg, seed), out_shardings=shardings)()


def leaf(params, path: str):
    """The stacked array at ``path`` (``shapes`` keys)."""
    if path in ("embed", "final_norm", "lm_head"):
        return params[path]
    node = params["segments"][0][0]
    for p in path.split("/"):
        node = node[p]
    return node


def nbytes(params) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(params))
