"""Random weights from the seed, made by the benchmark on the device in
one jitted call, in the types they are served in, in the parameter layout
the serving engine takes. The architecture's module (``arch/``) lists the
leaves (``shapes``) and places them in that tree (``nest``).

The same arrays feed the reference (``arch/<module>.py``) after the
program's state is freed: the reference reads them leaf by leaf and takes
nothing that the program made.
"""

from __future__ import annotations

import math

import jax

from benchmarks.chip import arch


def prng_key(seed: int):
    """A key for any whole-number seed (more than 32 bits included). The
    ``rbg`` generator: on a TPU it makes 11 GB of weights in a fraction of
    the time threefry takes, and the same seed gives the same weights."""
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def padded_vocab(vocab: int) -> int:
    """The engine's embedding and head rows: vocab rounded up to 256."""
    return -(-vocab // 256) * 256


NORM_SCALE = 0.1


def generate(cfg: dict, seed: int):
    """The whole parameter tree as one traceable function of the seed:
    each leaf of ``shapes``, in its order, drawn from the seed folded with
    its index."""
    mod = arch.find(cfg)
    key = prng_key(seed)
    flat = {}
    for i, (path, (shape, dt, scale)) in enumerate(mod.shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        # Uniform with unit variance, drawn in the served type.
        a = 3 ** 0.5 * (NORM_SCALE if scale is None else scale)
        flat[path] = jax.random.uniform(k, shape, dt, -a, a)
    return mod.nest(flat)


def make(cfg: dict, seed: int, shardings=None):
    """The weights on the device (``shardings``: a pytree of shardings to
    lay them out over a mesh; None = the default device)."""
    return jax.jit(lambda: generate(cfg, seed), out_shardings=shardings)()


def leaf(cfg: dict, params, path: str):
    """The array at ``path`` (a ``shapes`` key), wherever the
    architecture's ``nest`` puts it in ``params``."""
    mod = arch.find(cfg)
    names = jax.tree.leaves(mod.nest({p: p for p in mod.shapes(cfg)}))
    return jax.tree.leaves(params)[names.index(path)]


def nbytes(params) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(params))
