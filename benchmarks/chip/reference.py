"""What every architecture's plain float32 reference shares, and the
comparison of served tokens with any of them.

An architecture's reference (``arch/<module>.py``, ``Reference``) is its
forward written from the layer equations in ``jax.numpy`` at ``highest``
matmul precision, with no kernel, cache, batching or padding of the
program's, over the benchmark's own weights (``weights.py``). It has
``hidden(seqs, fp8, block)`` -> (packed final hidden states, each
sequence's first row, per row the smallest router margin over layers) and
``logits(hall, rows, fp8)``. ``fp8=True`` is its control: every matmul
operand rounded to float8 (e4m3, ``_q``), the step below the
configuration's bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q(x, fp8: bool, axis=None):
    """Round ``x`` to fp8 with a max-abs scale (over ``axis``; None = the
    whole tensor) when ``fp8``; identity otherwise."""
    if not fp8:
        return x
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(m, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1; rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@jax.jit
def _gap(logits, tok):
    """How far each token's logit lies below the row's best."""
    return logits.max(-1) - jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]


def _bucket(n: int, lo: int) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def served_gaps(ref, sample, fp8_control: bool = False):
    """For each request in ``sample`` (``(input ids, served tokens)``: the
    padded prompt as the engine ran it and every token it served), the gap
    by which each served token's reference logit lies below the
    reference's best at that position, and the position's router margin.

    With ``fp8_control`` the gaps are those of the tokens that the fp8
    control puts first at the same positions, on the same inputs.
    """
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in sample]
    hall, starts, margin = ref.hidden(seqs)
    rows = np.concatenate([a + len(p) - 1 + np.arange(len(o))
                           for a, (p, o) in zip(starts, sample)])
    n = len(rows)
    pad = _bucket(n, 256)
    rows_d = jnp.asarray(np.pad(rows, (0, pad - n)))
    lg = ref.logits(hall, rows_d)
    if fp8_control:
        del hall
        chall = ref.hidden(seqs, fp8=True)[0]
        tok = jnp.argmax(ref.logits(chall, rows_d, fp8=True), -1)
    else:
        tok = jnp.asarray(np.pad(np.concatenate(
            [np.asarray(o, np.int32) for _, o in sample]), (0, pad - n)))
    return np.asarray(_gap(lg, tok))[:n], margin[rows]
