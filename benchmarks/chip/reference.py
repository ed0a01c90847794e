"""Plain float32 reference forward of the served model, and its fp8 control.

Written from the layer equations the configuration states, in
``jax.numpy`` at ``highest`` matmul precision, with no kernel, cache,
batching or padding of the program's: token embedding scaled by
sqrt(hidden_size); per layer, RMSNorm (weight applied as 1 + w), grouped-
query attention with rotary positions (rotate-half, base ``rope_theta``),
causal over the whole sequence; RMSNorm and a softmax top-k router whose k
gates are renormalised to sum to 1, each routed expert a SwiGLU FFN; a
final RMSNorm and the output head. Nothing is imported from the program:
the weights are the benchmark's own arrays (``weights.py``), read layer by
layer and expert by expert, in float32, inside a few programs whose
shapes are padded to a handful of sizes.

``fp8=True`` is the control: every matmul operand rounded to float8
(e4m3) with a per-tensor scale for weights and a per-row scale for
activations, the step below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W

F8 = jnp.float8_e4m3fn
MOE_ROWS = 4096
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


def _w(stack, layer):
    """One layer of a stacked weight, in float32."""
    return jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False
                                        ).astype(jnp.float32)


@partial(jax.jit, static_argnames=("size", "scale"), donate_argnums=(0,))
def _embed(hall, start, emb, ids, *, size, scale):
    """Write one sequence's embeddings into its rows of the packed
    hidden states."""
    x = jnp.take(emb, ids, axis=0).astype(jnp.float32) * scale
    return jax.lax.dynamic_update_slice_in_dim(hall, x, start, 0)


@partial(jax.jit, static_argnames=("size", "eps", "theta", "fp8", "block"),
         donate_argnums=(0,))
def _attn(hall, start, layer, ln, wq, wk, wv, wo, *, size, eps, theta, fp8,
          block):
    """Causal GQA attention of one layer over one sequence: the ``size``
    rows of the packed hidden states from ``start``."""
    h = jax.lax.dynamic_slice_in_dim(hall, start, size)
    x = _q(_rms(h, _w(ln, layer), eps), fp8, -1)
    q = _rope(jnp.einsum("sd,dhk->shk", x, _q(_w(wq, layer), fp8)), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", x, _q(_w(wk, layer), fp8)), theta)
    v = jnp.einsum("sd,dhk->shk", x, _q(_w(wv, layer), fp8))
    hq, hkv, hd = q.shape[1], k.shape[1], q.shape[2]
    g = hq // hkv
    kq, vq = _q(k, fp8, -1), _q(v, fp8, -1)
    outs = []
    for b0 in range(0, size, block):                # query blocks: bounded
        qb = _q(q[b0:b0 + block], fp8, -1).reshape(-1, hkv, g, hd)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, kq) * hd ** -0.5
        qi = b0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(size)[None, :] <= qi, sc, -jnp.inf)
        p = _q(jax.nn.softmax(sc, -1), fp8, -1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, vq).reshape(-1, hq, hd))
    o = _q(jnp.concatenate(outs), fp8, -1)
    h = h + jnp.einsum("shk,hkd->sd", o, _q(_w(wo, layer), fp8))
    return jax.lax.dynamic_update_slice_in_dim(hall, h, start, 0)


@partial(jax.jit, static_argnames=("eps", "k", "fp8", "rows"),
         donate_argnums=(0,))
def _moe(hall, start, layer, ln, router, wg, wu, wd, *, eps, k, fp8, rows):
    """The expert layer over ``rows`` packed rows from ``start``: a softmax
    top-k router with renormalised gates, each expert's SwiGLU FFN
    weighted by its gate (zero where the row did not route to it). Also
    returns the gap between the k-th and (k+1)-th router logits of each
    row (a near tie where small)."""
    h = jax.lax.dynamic_slice_in_dim(hall, start, rows)
    x = _rms(h, _w(ln, layer), eps)
    logits = _q(x, fp8, -1) @ _q(_w(router, layer), fp8)
    probs = jax.nn.softmax(logits, -1)
    top_p, idx = jax.lax.top_k(probs, k)
    gates = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)
    dense = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(gates)
    top_l = jax.lax.top_k(logits, k + 1)[0]
    xq = _q(x, fp8, -1)

    def expert(y, e):
        def one(w):                 # one expert of one layer, in float32
            blk = jax.lax.dynamic_slice(w, (layer, e, 0, 0),
                                        (1, 1) + w.shape[2:])[0, 0]
            return _q(blk.astype(jnp.float32), fp8)
        a = jax.nn.silu(xq @ one(wg)) * (xq @ one(wu))
        return y + dense[:, e, None] * (_q(a, fp8, -1) @ one(wd)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(probs.shape[1]))
    return (jax.lax.dynamic_update_slice_in_dim(hall, h + y, start, 0),
            top_l[:, k - 1] - top_l[:, k])


@partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _head(hall, rows, ln, head, *, eps, vocab, fp8):
    h = _q(_rms(hall[rows], ln.astype(jnp.float32), eps), fp8, -1)
    return h @ _q(head[:, :vocab].astype(jnp.float32), fp8)


@jax.jit
def _gap(logits, tok):
    """How far each token's logit lies below the row's best."""
    return logits.max(-1) - jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]


def _bucket(n: int, lo: int) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


class Reference:
    """The reference over the benchmark's weights ``params`` (the arrays
    the engine was given; read layer by layer and expert by expert, in
    float32, inside each program)."""

    def __init__(self, cfg: dict, params):
        self.cfg, self.params = cfg, params
        self.eps = cfg["rms_norm_eps"]

    def _leaf(self, path):
        return W.leaf(self.params, path)

    def hidden(self, seqs, fp8: bool = False, block: int = 1024):
        """Final hidden states (before the last norm) of the sequences
        ``seqs`` (1-D int arrays), packed: each from its offset in
        ``starts``; and per row the smallest router margin over layers."""
        with jax.default_matmul_precision("highest"):
            return self._hidden(seqs, fp8, block)

    def _hidden(self, seqs, fp8, block):
        cfg = self.cfg
        sizes = [_bucket(len(s), 256) for s in seqs]
        starts = np.cumsum([0] + sizes)[:-1]
        # Rows padded to a multiple of MOE_ROWS keep the programs' shapes
        # few. Padding sits after each sequence: no position attends it.
        total = -(-int(sum(sizes)) // MOE_ROWS) * MOE_ROWS
        hall = jnp.zeros((total, cfg["hidden_size"]), jnp.float32)
        emb = self._leaf("embed")
        for s, a, n in zip(seqs, starts, sizes):
            hall = _embed(hall, jnp.int32(a), emb,
                          jnp.asarray(np.pad(s, (0, n - len(s)))), size=n,
                          scale=float(cfg["hidden_size"]) ** 0.5)
        attn = [self._leaf(f"attn/{n}") for n in ("wq", "wk", "wv", "wo")]
        moe = [self._leaf(p) for p in ("ln2", "moe/router",
                                       "moe/experts/w_gate",
                                       "moe/experts/w_up",
                                       "moe/experts/w_down")]
        margin = None
        for layer in range(cfg["num_hidden_layers"]):
            li = jnp.int32(layer)
            for a, n in zip(starts, sizes):
                hall = _attn(hall, jnp.int32(a), li, self._leaf("ln1"),
                             *attn, size=n, eps=self.eps,
                             theta=cfg["rope_theta"], fp8=fp8,
                             block=min(block, n))
            # The experts in blocks of rows, so their activations fit
            # beside the weights.
            ms = []
            for r0 in range(0, total, MOE_ROWS):
                hall, m = _moe(hall, jnp.int32(r0), li, *moe, eps=self.eps,
                               k=cfg["num_experts_per_tok"], fp8=fp8,
                               rows=MOE_ROWS)
                ms.append(m)
            m = jnp.concatenate(ms)
            margin = m if margin is None else jnp.minimum(margin, m)
        return hall, starts, np.asarray(margin)

    def logits(self, hall, rows, fp8: bool = False):
        with jax.default_matmul_precision("highest"):
            return _head(hall, rows, self._leaf("final_norm"),
                         self._leaf("lm_head"), eps=self.eps,
                         vocab=self.cfg["vocab_size"], fp8=fp8)


def served_gaps(ref: Reference, sample, fp8_control: bool = False):
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
