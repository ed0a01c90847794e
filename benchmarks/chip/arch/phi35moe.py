"""Phi-3.5-MoE as the program runs it: grouped-query attention and a
softmax top-k mixture of SwiGLU experts in every layer.

Configuration keys are those of the published ``config.json``
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``num_hidden_layers``, ``num_local_experts``,
``num_experts_per_tok``, ``vocab_size``, ``rope_theta``, ``rms_norm_eps``)
and the program's ``capacity_factor``; the head size is
``hidden_size / num_attention_heads``. Every expert is held here.

The reference forward is written from the layer equations, in
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

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W
from benchmarks.chip.reference import _bucket, _q, _rms, _rope
from benchmarks.chip.work import BYTES

ARCHS = ("phi3.5-moe-42b-a6.6b",)


# ---------------------------------------------------------------------------
# The program's configuration
# ---------------------------------------------------------------------------

def program_config(cfg: dict):
    """The engine's ``ModelConfig`` for the sizes in ``cfg``."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    moe = dataclasses.replace(
        base.moe, n_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff=cfg["intermediate_size"],
        capacity_factor=cfg["capacity_factor"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"], moe=moe)


def attn_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def experts(cfg: dict) -> tuple[int, int, int]:
    """(experts held here, experts routed over, experts per token)."""
    e = cfg["num_local_experts"]
    return e, e, cfg["num_experts_per_tok"]


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def tiny(cfg: dict) -> dict:
    """``cfg`` with every width and count cut to CPU size."""
    cfg = dict(cfg, hidden_size=256, intermediate_size=512,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, num_local_experts=4, vocab_size=512)
    # Readings at this size over seeds 1-6, 77 and 2**32 + 5: sound runs
    # 0 to 0.0227, the fp8 control 0.085 to 1.19 (test_chipbench_check).
    cfg["check"] = {"max_logit_gap": 0.045, "min_compared_tokens": 20,
                    "tie_margin": 0.05}
    return cfg


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def shapes(cfg: dict) -> dict:
    """{leaf path: (shape, dtype, scale)}: each weight is drawn with
    standard deviation ``scale``; None marks a norm weight (served as
    1 + w, drawn with deviation ``weights.NORM_SCALE`` around 0)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], cfg["hidden_size"] // h
    f, e = cfg["intermediate_size"], cfg["num_local_experts"]
    n, vp = cfg["num_hidden_layers"], W.padded_vocab(cfg["vocab_size"])
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


def nest(flat: dict) -> dict:
    """The leaves in the engine's tree: one segment of stacked layers."""
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


# ---------------------------------------------------------------------------
# Work counts (``work.py``)
# ---------------------------------------------------------------------------

def moe_ffn(cfg: dict, pairs: float, distinct: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the grouped expert FFN of ONE layer: gate, up and
    down matmuls over ``pairs`` routed rows; weights of ``distinct``
    experts read once, each routed row read in and written out once."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    b = BYTES[cfg["torch_dtype"]]
    flops = pairs * 3 * 2 * d * f
    nbytes = distinct * 3 * d * f * b + pairs * 2 * d * b
    return flops, nbytes


def decode_attn(cfg: dict, valid: float, slots: int) -> tuple[float, float]:
    """(FLOPs, bytes) of single-query attention of ONE layer over
    ``slots`` active slots whose valid cache lengths sum to ``valid``:
    scores and the weighted sum over every valid key; each valid key and
    value read once, each query read and output written once."""
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    b = BYTES[cfg["torch_dtype"]]
    flops = 4 * valid * h * hd
    nbytes = 2 * valid * hkv * hd * b + 2 * slots * h * hd * b
    return flops, nbytes


def token_flops(cfg: dict, context: float, logits: bool = True) -> float:
    """Model FLOPs of one token through every layer, attending ``context``
    earlier positions (itself included): projections, attention scores and
    sum, router, the top-k experts, and the output head when ``logits``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = d // h
    f, e, k = (cfg["intermediate_size"], cfg["num_local_experts"],
               cfg["num_experts_per_tok"])
    per_layer = (2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d
                 + 4 * context * h * hd + 2 * d * e + k * 3 * 2 * d * f)
    head = 2 * d * cfg["vocab_size"] if logits else 0
    return cfg["num_hidden_layers"] * per_layer + head


def prefill_flops(cfg: dict, start: int, n: int, last: bool) -> float:
    """Model FLOPs of prompt positions ``start .. start + n - 1`` (counted
    in real prompt tokens, causal context), with the output head only for
    the prompt's last position (``last``): that is all prefill needs."""
    if n <= 0:
        return 0.0
    mean_ctx = start + (n + 1) / 2
    out = n * token_flops(cfg, mean_ctx, logits=False)
    if last:
        out += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return out


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------

MOE_ROWS = 4096


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


class Reference:
    """The reference over the benchmark's weights ``params`` (the arrays
    the engine was given; read layer by layer and expert by expert, in
    float32, inside each program)."""

    def __init__(self, cfg: dict, params):
        self.cfg, self.params = cfg, params
        self.eps = cfg["rms_norm_eps"]

    def _leaf(self, path):
        return W.leaf(self.cfg, self.params, path)

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
