"""A second architecture for the tests only: Phi-3.5-MoE's layers with
leading dense layers (attention and a SwiGLU FFN of
``dense_intermediate_size``) and one shared expert in every MoE layer (a
SwiGLU FFN of ``shared_intermediate_size`` that every token takes beside
its routed experts). The program serves both (``MoEConfig``'s
``first_dense_layers`` and ``n_shared_experts``), as DeepSeek-V3 needs.

It lives beside the tests and not under ``arch/``, so no cell can find
it; ``test_chipbench_arch.py`` hands it to ``arch.find``. It shows that an
architecture whose parameters form two segments, and whose MoE layers
are fewer than its attention layers, runs through the harness with no
edit of a shared file. ``CONFIG`` is its configuration at CPU size.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W
from benchmarks.chip.arch import phi35moe as phi
from benchmarks.chip.reference import _bucket, _q, _rms

ARCHS = ("test-phi-dense-shared",)

CONFIG = {
    "arch": "test-phi-dense-shared",
    "hidden_size": 256,
    "intermediate_size": 512,
    "dense_intermediate_size": 768,
    "shared_intermediate_size": 384,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "num_hidden_layers": 3,
    "first_k_dense_replace": 1,
    "num_local_experts": 4,
    "num_experts_per_tok": 2,
    "vocab_size": 512,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05,
    "torch_dtype": "bfloat16",
    "capacity_factor": 2.0,
}

SEGMENTS = ("dense", "sparse")

moe_ffn = phi.moe_ffn
decode_attn = phi.decode_attn
experts = phi.experts
vocab = phi.vocab
attn_layers = phi.attn_layers


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def program_config(cfg: dict):
    base = phi.program_config(dict(cfg, arch=phi.ARCHS[0]))
    moe = dataclasses.replace(
        base.moe, n_shared_experts=1,
        shared_d_ff=cfg["shared_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["dense_intermediate_size"])
    return dataclasses.replace(base, moe=moe)


def tiny(cfg: dict) -> dict:
    # Readings at this size over seeds 1-6, 77 and 2**32 + 5: sound runs
    # 0 to 0.0150, the fp8 control 0.194 to 0.816 (test_chipbench_arch).
    return dict(cfg, check={"max_logit_gap": 0.045,
                            "min_compared_tokens": 20, "tie_margin": 0.05})


def shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], d // h
    f, e = cfg["intermediate_size"], cfg["num_local_experts"]
    fd, fs = cfg["dense_intermediate_size"], cfg["shared_intermediate_size"]
    nd = cfg["first_k_dense_replace"]
    dt = jnp.dtype(cfg["torch_dtype"])
    vp = W.padded_vocab(cfg["vocab_size"])
    out = {"embed": ((vp, d), dt, 0.02), "final_norm": ((d,), dt, None),
           "lm_head": ((d, vp), dt, d ** -0.5)}
    for seg, n in zip(SEGMENTS, (nd, moe_layers(cfg))):
        out |= {
            f"{seg}/ln1": ((n, d), dt, None),
            f"{seg}/ln2": ((n, d), dt, None),
            f"{seg}/attn/wq": ((n, d, h, hd), dt, d ** -0.5),
            f"{seg}/attn/wk": ((n, d, hkv, hd), dt, d ** -0.5),
            f"{seg}/attn/wv": ((n, d, hkv, hd), dt, d ** -0.5),
            f"{seg}/attn/wo": ((n, h, hd, d), dt, (h * hd) ** -0.5),
        }
    out |= {
        "dense/ffn/w_gate": ((nd, d, fd), dt, d ** -0.5),
        "dense/ffn/w_up": ((nd, d, fd), dt, d ** -0.5),
        "dense/ffn/w_down": ((nd, fd, d), dt, fd ** -0.5),
    }
    n = moe_layers(cfg)
    out |= {
        "sparse/moe/router": ((n, d, e), jnp.dtype("float32"), d ** -0.5),
        "sparse/moe/experts/w_gate": ((n, e, d, f), dt, d ** -0.5),
        "sparse/moe/experts/w_up": ((n, e, d, f), dt, d ** -0.5),
        "sparse/moe/experts/w_down": ((n, e, f, d), dt, f ** -0.5),
        "sparse/moe/shared/w_gate": ((n, d, fs), dt, d ** -0.5),
        "sparse/moe/shared/w_up": ((n, d, fs), dt, d ** -0.5),
        "sparse/moe/shared/w_down": ((n, fs, d), dt, fs ** -0.5),
    }
    return out


def nest(flat: dict) -> dict:
    """Two segments: the dense layers, then the MoE layers."""
    segs = {s: {} for s in SEGMENTS}
    for path, x in flat.items():
        seg, _, rest = path.partition("/")
        if seg not in segs:
            continue
        node = segs[seg]
        *parents, leaf = rest.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"],
            "segments": tuple((segs[s],) for s in SEGMENTS)}


def token_flops(cfg: dict, context: float, logits: bool = True) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, hd = cfg["num_key_value_heads"], d // h
    f, e, k = (cfg["intermediate_size"], cfg["num_local_experts"],
               cfg["num_experts_per_tok"])
    attn = (2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d
            + 4 * context * h * hd)
    dense = 3 * 2 * d * cfg["dense_intermediate_size"]
    moe = (2 * d * e + k * 3 * 2 * d * f
           + 3 * 2 * d * cfg["shared_intermediate_size"])
    head = 2 * d * cfg["vocab_size"] if logits else 0
    return (attn_layers(cfg) * attn + cfg["first_k_dense_replace"] * dense
            + moe_layers(cfg) * moe + head)


def prefill_flops(cfg: dict, start: int, n: int, last: bool) -> float:
    if n <= 0:
        return 0.0
    out = n * token_flops(cfg, start + (n + 1) / 2, logits=False)
    if last:
        out += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return out


def _ffn(x, wg, wu, wd, fp8):
    """A SwiGLU FFN of float32 weights on normed rows ``x``."""
    a = jax.nn.silu(x @ _q(wg, fp8)) * (x @ _q(wu, fp8))
    return _q(a, fp8, -1) @ _q(wd, fp8)


@partial(jax.jit, static_argnames=("eps", "fp8", "rows"), donate_argnums=(0,))
def _dense(hall, start, layer, ln, wg, wu, wd, *, eps, fp8, rows):
    """A dense layer's FFN over ``rows`` packed rows from ``start``."""
    h = jax.lax.dynamic_slice_in_dim(hall, start, rows)
    x = _q(_rms(h, phi._w(ln, layer), eps), fp8, -1)
    y = _ffn(x, phi._w(wg, layer), phi._w(wu, layer), phi._w(wd, layer), fp8)
    return jax.lax.dynamic_update_slice_in_dim(hall, h + y, start, 0)


@partial(jax.jit, static_argnames=("eps", "k", "fp8", "rows"),
         donate_argnums=(0,))
def _moe(hall, start, layer, ln, router, wg, wu, wd, sg, su, sd, *, eps, k,
         fp8, rows):
    """An MoE layer over ``rows`` packed rows from ``start``: the shared
    expert plus the softmax top-k routed experts with renormalised gates;
    and each row's gap between its k-th and (k+1)-th router logits."""
    h = jax.lax.dynamic_slice_in_dim(hall, start, rows)
    x = _rms(h, phi._w(ln, layer), eps)
    logits = _q(x, fp8, -1) @ _q(phi._w(router, layer), fp8)
    top_p, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    gates = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)
    dense = jnp.zeros_like(logits).at[
        jnp.arange(rows)[:, None], idx].set(gates)
    xq = _q(x, fp8, -1)
    y = _ffn(xq, phi._w(sg, layer), phi._w(su, layer), phi._w(sd, layer), fp8)
    for e in range(logits.shape[1]):
        w = [phi._w(s, layer)[e] for s in (wg, wu, wd)]
        y = y + dense[:, e, None] * _ffn(xq, *w, fp8)
    top_l = jax.lax.top_k(logits, k + 1)[0]
    return (jax.lax.dynamic_update_slice_in_dim(hall, h + y, start, 0),
            top_l[:, k - 1] - top_l[:, k])


class Reference:
    """The float32 forward over the benchmark's weights ``params``."""

    def __init__(self, cfg: dict, params):
        self.cfg, self.params = cfg, params
        self.eps = cfg["rms_norm_eps"]

    def _leaf(self, path):
        return W.leaf(self.cfg, self.params, path)

    def hidden(self, seqs, fp8: bool = False, block: int = 1024):
        with jax.default_matmul_precision("highest"):
            return self._hidden(seqs, fp8, block)

    def _hidden(self, seqs, fp8, block):
        cfg = self.cfg
        sizes = [_bucket(len(s), 256) for s in seqs]
        starts = np.cumsum([0] + sizes)[:-1]
        total = int(sum(sizes))
        hall = jnp.zeros((total, cfg["hidden_size"]), jnp.float32)
        for s, a, n in zip(seqs, starts, sizes):
            hall = phi._embed(hall, jnp.int32(a), self._leaf("embed"),
                              jnp.asarray(np.pad(s, (0, n - len(s)))),
                              size=n, scale=float(cfg["hidden_size"]) ** 0.5)
        margin = jnp.full((total,), jnp.inf)
        counts = (cfg["first_k_dense_replace"], moe_layers(cfg))
        for seg, count in zip(SEGMENTS, counts):
            attn = [self._leaf(f"{seg}/attn/{n}")
                    for n in ("wq", "wk", "wv", "wo")]
            for layer in range(count):
                li = jnp.int32(layer)
                for a, n in zip(starts, sizes):
                    hall = phi._attn(hall, jnp.int32(a), li,
                                     self._leaf(f"{seg}/ln1"), *attn, size=n,
                                     eps=self.eps, theta=cfg["rope_theta"],
                                     fp8=fp8, block=min(block, n))
                ln2 = self._leaf(f"{seg}/ln2")
                if seg == "dense":
                    ffn = [self._leaf(f"dense/ffn/{w}")
                           for w in ("w_gate", "w_up", "w_down")]
                    hall = _dense(hall, jnp.int32(0), li, ln2, *ffn,
                                  eps=self.eps, fp8=fp8, rows=total)
                    continue
                moe = [self._leaf(f"sparse/moe/{p}") for p in (
                    "router", "experts/w_gate", "experts/w_up",
                    "experts/w_down", "shared/w_gate", "shared/w_up",
                    "shared/w_down")]
                hall, m = _moe(hall, jnp.int32(0), li, ln2, *moe,
                               eps=self.eps, k=cfg["num_experts_per_tok"],
                               fp8=fp8, rows=total)
                margin = jnp.minimum(margin, m)
        return hall, starts, np.asarray(margin)

    def logits(self, hall, rows, fp8: bool = False):
        with jax.default_matmul_precision("highest"):
            return phi._head(hall, rows, self._leaf("final_norm"),
                             self._leaf("lm_head"), eps=self.eps,
                             vocab=self.cfg["vocab_size"], fp8=fp8)
