"""Smoke run of the MoE serving path on a TPU.

Serves phi3.5-moe-42b-a6.6b at its published widths (d_model 4096, 32
heads, 8 KV heads, head_dim 128, 16 experts top-2 with expert d_ff 6400,
vocab 32064, bf16) with its depth cut to what one v5e chip holds, through
the public serving API: ``ContinuousEngine(model, params, ...,
config=EngineConfig(kernels=...))``. Weights are random, made from
``--seed``. Run it from the root of the checkout:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # expert parallelism over four chips

One chip, in order (any failure exits non-zero):

1. refuse to run unless JAX's first device is a TPU;
2. build the depth-cut config and its weights; print the cut and the bytes;
3. compile the engine's prefill and decode programs, print each compile
   time, and check that both hold a Pallas kernel (``tpu_custom_call``);
4. serve 8 requests of 64-256 prompt tokens and 32 new tokens on 8 slots
   with cache capacity 2048; every request must complete;
5. compare prefill and decode logits of the kernel path with the dense XLA
   path on the same weights (``compare_logits`` states the tolerance).

``--four-chips`` runs only the expert-parallel phase: the same config, made
dropless, served by ``DistributedEngine`` over ``make_ep_mesh(4)``, once
with the Aurora ppermute rounds and once with ``all_to_all``, each compared
with the one-chip kernel path of the same process.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
This is a smoke test: no time it prints is a throughput or latency result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ARCH = "phi3.5-moe-42b-a6.6b"
# Four of the 32 layers, the benchmark's cut: the kernel path's decode
# program needs the weights (10.93 GB at four layers, 2.6 GB more per
# layer) and the 0.27 GB cache as arguments plus 0.34 GB of scratch
# (``compiled.memory_analysis()`` against a described v5e): 11.5 GB. With
# a fifth layer it would need 14.3 GB.
N_LAYERS = 4
SLOTS = 8
CACHE_CAP = 2048
PREFILL_LEN = 256             # every prompt is left-padded to this length
NEW_TOKENS = 32
COMPARE_STEPS = 4             # decode steps whose logits are compared

# Tolerance of the logits comparison. bf16 rounds at 2^-8 ~ 3.9e-3 relative,
# and the two paths round at different points: the fused FFN keeps the gate
# and up products in f32 where XLA rounds them to bf16, and the decode
# kernel rounds the softmax weights to bf16 before the value matmul. Over
# four layers of attention + MoE that compounds to about 1e-2 of a logits
# row's norm; ROW_RTOL leaves headroom above it, while a wrong kernel (bad
# block, mask or expert) moves every row by O(1). A near-tie in a token's
# top-2 router scores can legitimately pick a different expert on the two
# paths and change that row by O(1), so a small share of rows may fail.
ROW_RTOL = 5e-2
MIN_ROWS_WITHIN = 0.9


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Phases: each takes a config and a KernelConfig, so a test can run them at
# ``reduced()`` size on the CPU with ``KernelConfig(interpret=True)``.
# ---------------------------------------------------------------------------

def smoke_config(n_layers: int = N_LAYERS):
    """phi3.5-moe at published widths, depth cut to ``n_layers``."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), n_layers=n_layers)


def init_params(cfg, seed: int, shardings=None):
    """Random weights made on the device (``shardings``: a pytree of
    ``NamedSharding`` to lay them out over a mesh; None = default device)."""
    import jax
    from repro.models import Model

    return jax.jit(Model(cfg).init, out_shardings=shardings)(
        jax.random.PRNGKey(seed))


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def make_prompts(cfg, seed: int, n: int, lens):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab, int(rng.integers(lens[0],
                                                             lens[1] + 1))))
            for _ in range(n)]


def make_engine(cfg, params, kc, *, slots=SLOTS, cap=CACHE_CAP,
                prefill_len=PREFILL_LEN, mesh=None, moe_impl="aurora",
                rounds=None):
    """The serving engine a user would build: ``kc`` (a ``KernelConfig``)
    selects the Pallas path, ``kc=None`` the dense XLA reference; ``mesh``
    serves expert-parallel through ``DistributedEngine``."""
    from repro.models import Model
    from repro.serving import ContinuousEngine, DistributedEngine, EngineConfig

    config = EngineConfig(prefill_len=prefill_len,
                          kernels=kc if kc is not None else False)
    if mesh is None:
        return ContinuousEngine(Model(cfg), params, slots, cap, config=config)
    return DistributedEngine(Model(cfg), params, slots, cap, mesh=mesh,
                             moe_impl=moe_impl, rounds=rounds, config=config)


def compile_programs(eng, prefill_len=PREFILL_LEN) -> dict:
    """Compile the engine's own prefill and decode programs ahead of time.
    Returns ``{name: (compile seconds, count of tpu_custom_call)}``; with
    the persistent compile cache warm, the seconds are a cache load."""
    import jax
    import jax.numpy as jnp

    args = {
        "prefill": (eng._prefill, eng.params,
                    {"tokens": jax.ShapeDtypeStruct((1, prefill_len),
                                                    jnp.int32)},
                    eng.cache, jnp.int32(0)),
        "decode": (eng._decode, eng.params, eng.tokens, eng.cache,
                   jnp.ones((eng.batch_slots,), bool)),
    }
    out = {}
    for name, (fn, *a) in args.items():
        t0 = time.perf_counter()
        hlo = fn.lower(*a).compile().as_text()
        out[name] = (time.perf_counter() - t0,
                     hlo.count('custom_call_target="tpu_custom_call"'))
    return out


def serve_requests(eng, prompts, new_tokens=NEW_TOKENS):
    """Serve one request per prompt to completion; returns the requests."""
    from repro.serving import Request

    reqs = [Request(prompt=p, max_new_tokens=new_tokens) for p in prompts]
    eng.serve(reqs)
    return reqs


def step_logits(eng, prompts, steps=COMPARE_STEPS, feed=None):
    """Logits from the engine's compiled programs: prompt ``i`` is
    prefilled into slot ``i``, then ``steps`` decode steps run over all
    slots. Decode inputs are ``feed`` (a list of (B,) token arrays) when
    given, else this engine's own greedy tokens, so two engines compared
    with one's feed see the same inputs. Returns ``(rows, feed)``: ``rows``
    is a list of (n, vocab) float32 arrays, first the prefill positions of
    every prompt (left padding dropped), then one per decode step."""
    import jax.numpy as jnp

    vocab, b = eng.model.cfg.vocab, eng.batch_slots
    p_len = eng.prefill_len
    prefill, last = [], np.zeros((b,), np.int32)
    for i, prompt in enumerate(prompts):
        toks = np.zeros((1, p_len), np.int32)
        toks[0, p_len - len(prompt):] = prompt
        logits, eng.cache = eng._prefill(eng.params,
                                         {"tokens": jnp.asarray(toks)},
                                         eng.cache, jnp.int32(i))
        lg = np.asarray(logits[0, :, :vocab], np.float32)
        prefill.append(lg[p_len - len(prompt):])
        last[i] = lg[-1].argmax()
    rows, fed = [np.concatenate(prefill)], []
    tokens = feed[0] if feed is not None else last
    mask = jnp.asarray(np.arange(b) < len(prompts))
    for s in range(steps):
        fed.append(tokens)
        logits, eng.cache = eng._decode(eng.params,
                                        jnp.asarray(tokens)[:, None],
                                        eng.cache, mask)
        lg = np.asarray(logits[:len(prompts), 0, :vocab], np.float32)
        rows.append(lg)
        if s + 1 < steps:
            tokens = (feed[s + 1] if feed is not None else
                      np.pad(lg.argmax(-1), (0, b - len(prompts))
                             ).astype(np.int32))
    return rows, fed


def compare_logits(ref, got) -> dict:
    """Per-row relative L2 error of ``got`` against ``ref`` (lists of
    (n, vocab) arrays from ``step_logits``), split into the prefill rows
    and the decode rows. Each part passes when at least ``MIN_ROWS_WITHIN``
    of its rows are within ``ROW_RTOL`` (see the reasoning above them).
    ``agree`` is the share of rows whose greedy token matches."""
    out = {}
    parts = {"prefill": (ref[:1], got[:1]), "decode": (ref[1:], got[1:])}
    for name, (r, g) in parts.items():
        r, g = np.concatenate(r), np.concatenate(g)
        err = (np.linalg.norm(g - r, axis=-1)
               / np.maximum(np.linalg.norm(r, axis=-1), 1e-30))
        within = float(np.mean(err <= ROW_RTOL))
        out[name] = {"rows": len(err), "median": float(np.median(err)),
                     "p90": float(np.quantile(err, 0.9)),
                     "max": float(err.max()), "within": within,
                     "agree": float(np.mean(r.argmax(-1) == g.argmax(-1))),
                     "ok": within >= MIN_ROWS_WITHIN}
    return out


def report_comparison(label: str, cmp: dict) -> bool:
    for part, c in cmp.items():
        log(f"{label} {part}: {c['rows']} rows, rel err median "
            f"{c['median']:.3e} p90 {c['p90']:.3e} max {c['max']:.3e}; "
            f"{c['within']:.4f} of rows within {ROW_RTOL:g} (need "
            f"{MIN_ROWS_WITHIN:g}); greedy-token agreement {c['agree']:.4f}"
            f" -> {'ok' if c['ok'] else 'FAIL'}")
    return all(c["ok"] for c in cmp.values())


def stream_agreement(ref_reqs, reqs) -> float:
    """Share of served token positions equal to the reference streams."""
    pairs = [(a, b) for r, q in zip(ref_reqs, reqs)
             for a, b in zip(r.out_tokens, q.out_tokens)]
    return float(np.mean([a == b for a, b in pairs])) if pairs else 0.0


def free(*trees) -> None:
    """Release device buffers now rather than at garbage collection."""
    import jax

    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()


def one_chip_phase(cfg, kc, seed: int, *, slots=SLOTS, cap=CACHE_CAP,
                   prefill_len=PREFILL_LEN, new_tokens=NEW_TOKENS,
                   steps=COMPARE_STEPS) -> bool:
    """Steps 2-5 of the module docstring. Returns True when all passed.
    Compiled Pallas kernels must show in both programs; interpret mode
    (``kc.interpret``, a CPU rehearsal) lowers them to plain XLA."""
    params = init_params(cfg, seed)
    log(f"parameters: {tree_bytes(params)} bytes "
        f"({cfg.param_count()} params, {cfg.dtype})")
    eng = make_engine(cfg, params, kc, slots=slots, cap=cap,
                      prefill_len=prefill_len)
    ok = True
    for name, (sec, n_kernels) in compile_programs(eng, prefill_len).items():
        log(f"compile {name}: {sec:.1f} s, {n_kernels} tpu_custom_call")
        if not kc.interpret and n_kernels == 0:
            log(f"FAIL: the compiled {name} program runs no Pallas kernel")
            ok = False

    prompts = make_prompts(cfg, seed, n=slots,
                           lens=(prefill_len // 4, prefill_len))
    t0 = time.perf_counter()
    reqs = serve_requests(eng, prompts, new_tokens)
    done = sum(len(r.out_tokens) == new_tokens for r in reqs)
    log(f"served {len(reqs)} requests ({sum(map(len, prompts))} prompt "
        f"tokens, {sum(len(r.out_tokens) for r in reqs)} new tokens) in "
        f"{time.perf_counter() - t0:.1f} s including compilation; "
        f"{done}/{len(reqs)} complete")
    ok &= done == len(reqs)

    got, feed = step_logits(eng, prompts, steps)
    free(eng.cache)
    dense = make_engine(cfg, params, None, slots=slots, cap=cap,
                        prefill_len=prefill_len)
    ref, _ = step_logits(dense, prompts, steps, feed=feed)
    free(dense.cache)
    ok &= report_comparison("kernel vs dense", compare_logits(ref, got))
    return ok


def expert_placement(params, n_devices: int) -> dict:
    """``{device id: expert index range}`` of every expert leaf's shards;
    raises unless the experts split evenly over ``n_devices`` devices."""
    import jax

    ranges = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if "experts" not in jax.tree_util.keystr(path):
            continue
        n_e = leaf.shape[-3]
        for shard in leaf.addressable_shards:
            sl = shard.index[leaf.ndim - 3]
            rng = range(n_e)[sl]
            prev = ranges.setdefault(shard.device.id, (rng.start, rng.stop))
            if prev != (rng.start, rng.stop):
                raise AssertionError(f"expert leaves disagree on device "
                                     f"{shard.device.id}: {prev} vs {rng}")
    sizes = {b - a for a, b in ranges.values()}
    if len(ranges) != n_devices or sizes != {n_e // n_devices}:
        raise AssertionError(f"{n_e} experts are not split {n_e // n_devices}"
                             f" per device over {n_devices}: {ranges}")
    return ranges


def four_chip_phase(cfg, kc, seed: int, *, n_devices: int = 4, slots=SLOTS,
                    cap=CACHE_CAP, prefill_len=PREFILL_LEN,
                    new_tokens=NEW_TOKENS, steps=COMPARE_STEPS) -> bool:
    """Expert-parallel serving over ``n_devices`` chips, Aurora rounds and
    ``all_to_all``, each compared with the one-chip kernel path."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core import synthetic_trace
    from repro.launch.mesh import make_ep_mesh
    from repro.serving import rounds_from_trace
    from repro.sharding import param_specs

    # Expert parallelism sizes each expert's capacity per source device,
    # the one-chip path over the whole token group, so at any capacity that
    # can drop tokens the two drop different ones and their logits differ
    # by O(1). At capacity factor E/k neither drops (``capacity`` clamps to
    # the token count), so the comparison checks the exchange, not the
    # drop policy.
    moe = dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k)
    log(f"capacity factor {cfg.moe.capacity_factor:g}->"
        f"{moe.capacity_factor:g}: dropless on both paths")
    cfg = dataclasses.replace(cfg, moe=moe)
    prompts = make_prompts(cfg, seed, n=slots,
                           lens=(prefill_len // 4, prefill_len))
    # The reference holds every weight on one chip; it is freed before the
    # sharded copy is made.
    params = init_params(cfg, seed)
    ref_eng = make_engine(cfg, params, kc, slots=slots, cap=cap,
                          prefill_len=prefill_len)
    ref, feed = step_logits(ref_eng, prompts, steps)
    ref_reqs = serve_requests(ref_eng, prompts, new_tokens)
    free(params, ref_eng.cache)
    del ref_eng

    mesh = make_ep_mesh(n_devices, devices=jax.devices()[:n_devices])
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(cfg, mesh))
    params = init_params(cfg, seed, shardings)
    placement = expert_placement(params, n_devices)
    log("experts per device: " + ", ".join(
        f"device {d}: {a}..{b - 1}" for d, (a, b) in sorted(placement.items())))

    hist = synthetic_trace("hist", n_experts=cfg.moe.n_experts, n_layers=2,
                           seed=0)
    ok, outs = True, {}
    for impl in ("aurora", "ep"):
        rounds = rounds_from_trace(hist, n_devices) if impl == "aurora" \
            else None
        eng = make_engine(cfg, params, kc, slots=slots, cap=cap,
                          prefill_len=prefill_len, mesh=mesh, moe_impl=impl,
                          rounds=rounds)
        t0 = time.perf_counter()
        got, _ = step_logits(eng, prompts, steps, feed=feed)
        reqs = serve_requests(eng, prompts, new_tokens)
        done = sum(len(r.out_tokens) == new_tokens for r in reqs)
        log(f"{impl}: {len(rounds or ())} scheduled rounds; served "
            f"{done}/{len(reqs)} requests complete in "
            f"{time.perf_counter() - t0:.1f} s including compilation; "
            f"served-token agreement with one chip "
            f"{stream_agreement(ref_reqs, reqs):.4f}")
        ok &= done == len(reqs)
        ok &= report_comparison(f"{impl} vs one chip",
                                compare_logits(ref, got))
        outs[impl] = got
        free(eng.cache)
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(outs["aurora"], outs["ep"]))
    log(f"aurora vs ep: max |logit difference| {diff:.3e}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip expert-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform}; nothing was run", file=sys.stderr)
        return 2
    n_need = 4 if args.four_chips else 1
    if len(jax.devices()) < n_need:
        print(f"chip_smoke: needs {n_need} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.layers import KernelConfig

    cache_dir = enable_compile_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries = cache_entries()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}")
    log(f"compile cache: {cache_dir} ({entries} entries at start: "
        f"{'warm' if entries else 'cold'})")

    cfg = smoke_config()
    log(f"config: {cfg.arch_id} n_layers 32->{cfg.n_layers} (depth cut), "
        f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} kv, "
        f"head_dim {cfg.head_dim}, experts {cfg.moe.n_experts} top-"
        f"{cfg.moe.top_k}, expert d_ff {cfg.moe.d_ff}, vocab {cfg.vocab}, "
        f"{cfg.dtype}")
    kc = KernelConfig()
    t0 = time.perf_counter()
    if args.four_chips:
        ok = four_chip_phase(cfg, kc, args.seed)
    else:
        ok = one_chip_phase(cfg, kc, args.seed)
    mem = dev.memory_stats() or {}
    log(f"wall time {time.perf_counter() - t0:.1f} s; device 0 peak memory "
        f"{mem.get('peak_bytes_in_use')} of {mem.get('bytes_limit')} bytes")
    # A program found in the cache is loaded, not written: entries written
    # by this run are the programs it had to compile.
    log(f"compile cache: {cache_entries() - entries} entries written by "
        f"this run")
    if not ok:
        log("chip_smoke: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
