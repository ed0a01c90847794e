"""Serving driver: single-model or Aurora-colocated dual-model, static batch
or continuous batching with a streaming (Poisson) arrival process.

  python -m repro.launch.serve --arch qwen3-32b --reduced
  python -m repro.launch.serve --arch qwen3-32b --reduced \
      --arrival-rate 0.5 --num-requests 12          # continuous batching
  python -m repro.launch.serve --arch phi3.5-moe-42b-a6.6b \
      --colocate-with phi4-mini-3.8b --reduced --arrival-rate 0.5
  python -m repro.launch.serve --arch phi3.5-moe-42b-a6.6b --reduced \
      --experts 8 --arrival-rate 0.5 --mesh 8 --overlap   # distributed EP

``--arrival-rate λ`` switches to the continuous engine and draws request
inter-arrival gaps from Exp(λ) (a Poisson process), measured in decode-step
time units — the serving-loop clock. The colocated mode plans the expert
pairing with AuroraPlanner from a synthetic routing trace, permutes model B's
experts accordingly, and serves both streams through one interleaved XLA
program (see serving/colocated.py).

``--ttft-slo`` / ``--tpot-slo`` declare per-tenant SLO targets (p95, in
engine-step units): each served model gets a ``TenantSpec``, every request's
deadline is stamped from it at submit, and admission switches to
deadline-aware EDF (``EdfAdmission`` — earliest effective deadline first,
starvation-free via aging) over the same chunk and budget:

  python -m repro.launch.serve --arch qwen3-32b --reduced \
      --arrival-rate 0.5 --prefill-chunk 4 --ttft-slo 12 --tpot-slo 2

``--mesh N`` serves EP-sharded over an N-device mesh (on a CPU host the
platform is split into N virtual devices — the flag must land before jax
initializes, which is why it is handled first). ``--moe-impl aurora``
(default) dispatches through the scheduled ppermute rounds, planned from a
synthetic historical trace; ``--overlap`` pipelines expert FFN chunks with
in-flight rounds (repro.distributed.overlap). The expert count must divide
N — use ``--experts`` to widen the reduced configs.

``--trace-out BASE`` / ``--metrics-out PATH`` attach the unified telemetry
hub (serving/telemetry.py) to whichever engine is built: structured spans
(engine_step > admit / prefill_chunk / decode_step / sample / readback /
emit) and the typed event bus (replan / shed / fault / adoption) land in
``BASE.jsonl`` and
``BASE.trace.json`` (Chrome trace-event JSON — open in Perfetto), and the
final metrics snapshot (tok/s, TTFT, expert-load imbalance, …) is written
as JSON on exit — including on Ctrl-C.
"""

from __future__ import annotations

import argparse

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--colocate-with", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--cache-cap", type=int, default=64)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="requests per decode step (Poisson); enables "
                         "continuous batching")
    ap.add_argument("--num-requests", type=int, default=12,
                    help="stream length for --arrival-rate mode")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: absorb at most N prompt tokens "
                         "per engine step (continuous engines)")
    ap.add_argument("--step-budget", type=int, default=None,
                    help="per-step token budget: decode always runs, "
                         "leftover feeds the FIFO prefix of due prefill "
                         "chunks")
    ap.add_argument("--prefill-pool", type=int, default=1,
                    help="admit up to K chunked prefills concurrently; "
                         "their chunks (and decode) fuse into one jitted "
                         "step (requires --prefill-chunk)")
    ap.add_argument("--bucket-policy", default="pow2",
                    help="prefill pad-length policy: pow2 | exact | step:K")
    ap.add_argument("--replan-interval", type=int, default=None,
                    help="colocated continuous mode: re-plan the expert "
                         "pairing from live routing stats every N decode "
                         "steps")
    ap.add_argument("--replan-threshold", type=float, default=0.02,
                    help="min relative predicted-time improvement before a "
                         "re-plan is applied")
    ap.add_argument("--ttft-slo", type=float, default=None,
                    help="p95 TTFT target in engine steps: declares a "
                         "TenantSpec SLO (stamps per-request deadlines) and "
                         "switches admission to deadline-aware EDF")
    ap.add_argument("--tpot-slo", type=float, default=None,
                    help="p95 TPOT target in engine steps (declared on the "
                         "TenantSpec next to --ttft-slo)")
    ap.add_argument("--kernels", action="store_true",
                    help="continuous engines: serve through the Pallas "
                         "kernel path (sort-based ragged MoE dispatch + "
                         "flash-decode attention; pure-jnp twin on CPU)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="serve EP-sharded over an N-device mesh (forces N "
                         "host-platform devices on CPU; the expert count "
                         "must divide N)")
    ap.add_argument("--moe-impl", default=None,
                    choices=["ep", "aurora"],
                    help="--mesh dispatch path: monolithic all_to_all (ep) "
                         "or scheduled ppermute rounds (aurora)")
    ap.add_argument("--overlap", action="store_true",
                    help="--mesh: round-pipelined dispatch — expert FFN "
                         "chunks overlap in-flight ppermute rounds")
    ap.add_argument("--experts", type=int, default=None,
                    help="override the MoE expert count (reduced configs "
                         "clamp to 4, which rarely divides a mesh)")
    ap.add_argument("--trace-out", default=None, metavar="BASE",
                    help="record telemetry and write BASE.jsonl (structured "
                         "spans + events) and BASE.trace.json (Chrome "
                         "trace-event JSON — open in Perfetto) on exit")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metrics snapshot as JSON on exit "
                         "(also on Ctrl-C)")
    args = ap.parse_args()

    if args.mesh is None and (args.overlap or args.moe_impl is not None):
        # Fail loudly: without a mesh these flags would silently serve the
        # single-device dense path while the user believes they measured
        # distributed dispatch.
        raise SystemExit("--overlap/--moe-impl configure the distributed "
                         "EP dispatch; add --mesh N (or drop them)")
    if args.mesh is not None:
        # Before jax initializes: split the host platform into the mesh's
        # device count (no-op when real devices exist and the flag is set).
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.mesh)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    telemetry = None
    if args.trace_out or args.metrics_out:
        from repro.serving.telemetry import Telemetry
        telemetry = Telemetry()

    # The flush runs on every exit path — clean return, SystemExit, and
    # Ctrl-C — so a long serving run killed mid-stream still leaves its
    # trace and metrics on disk.
    try:
        return _serve(args, telemetry)
    except KeyboardInterrupt:
        print("\ninterrupted")
        return 130
    finally:
        _flush_telemetry(telemetry, args)


def _flush_telemetry(telemetry, args) -> None:
    if telemetry is None:
        return
    if args.trace_out:
        telemetry.write_jsonl(args.trace_out + ".jsonl")
        telemetry.write_chrome_trace(args.trace_out + ".trace.json")
        print(f"trace: {args.trace_out}.jsonl + {args.trace_out}.trace.json"
              f" (open the .trace.json in Perfetto / chrome://tracing)")
    if args.metrics_out:
        import json
        with open(args.metrics_out, "w") as f:
            json.dump(telemetry.snapshot(), f, indent=2, sort_keys=True)
        print(f"metrics snapshot: {args.metrics_out}")


def _serve(args, telemetry) -> int:
    import jax
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import (ColocatedContinuousEngine, ColocatedEngine,
                               ContinuousEngine, EdfAdmission, EngineConfig,
                               Request, ServingEngine, TenantSpec,
                               poisson_requests)

    # One config for every continuous engine this driver can build. SLO
    # flags declare TenantSpecs (one per served model — they stamp each
    # request's deadline) and replace the chunk/budget shorthand with
    # deadline-aware EDF admission over the same chunk and budget.
    slo = args.ttft_slo is not None or args.tpot_slo is not None
    if slo:
        names = [args.arch] + ([args.colocate_with] if args.colocate_with
                               else [])
        tenants = tuple(TenantSpec(name=name, ttft_p95=args.ttft_slo,
                                   tpot_p95=args.tpot_slo)
                        for name in names)
        config = EngineConfig(
            prefill_len=args.prompt_len,
            admission=EdfAdmission(
                chunk=args.prefill_chunk or args.prompt_len,
                budget=args.step_budget,
                bucket_policy=args.bucket_policy),
            prefill_pool=args.prefill_pool, kernels=args.kernels,
            tenants=tenants, telemetry=telemetry)
        print(f"SLO targets (engine steps): ttft_p95<="
              f"{args.ttft_slo if args.ttft_slo is not None else 'none'} "
              f"tpot_p95<="
              f"{args.tpot_slo if args.tpot_slo is not None else 'none'} "
              f"-> EDF admission, {len(tenants)} tenant spec(s)")
    else:
        config = EngineConfig(prefill_len=args.prompt_len,
                              prefill_chunk=args.prefill_chunk,
                              step_token_budget=args.step_budget,
                              bucket_policy=args.bucket_policy,
                              prefill_pool=args.prefill_pool,
                              kernels=args.kernels, telemetry=telemetry)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.experts is not None:
        import dataclasses
        if cfg.moe is None:
            raise SystemExit(f"{args.arch} has no MoE layers to widen")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=args.experts))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    moe_impl = args.moe_impl or "aurora"
    mesh = None
    if args.mesh is not None:
        if args.arrival_rate is None:
            raise SystemExit("--mesh serves through the continuous engines; "
                             "add --arrival-rate")
        from repro.launch.mesh import make_ep_mesh
        mesh = make_ep_mesh(args.mesh)

    if args.colocate_with is None:
        if args.arrival_rate is not None:
            kw = dict(batch_slots=args.batch, cache_cap=args.cache_cap,
                      config=config)
            if mesh is not None:
                from repro.core import synthetic_trace
                from repro.serving import (DistributedEngine,
                                           rounds_from_trace)
                if cfg.moe is None:
                    raise SystemExit(
                        f"{args.arch} has no MoE layers — --mesh serves "
                        "expert-parallel (nothing to shard); drop --mesh or "
                        "pick an MoE arch")
                n = cfg.moe.n_experts
                hist = synthetic_trace("hist", n_experts=n, n_layers=2,
                                       seed=0)
                rounds = (rounds_from_trace(hist, args.mesh)
                          if moe_impl == "aurora" else None)
                eng = DistributedEngine(model, params, mesh=mesh,
                                        moe_impl=moe_impl,
                                        rounds=rounds, overlap=args.overlap,
                                        **kw)
                print(f"distributed EP serving: {args.mesh}-device mesh, "
                      f"impl={moe_impl}, overlap={args.overlap}, "
                      f"{len(rounds or ())} scheduled rounds")
            else:
                eng = ContinuousEngine(model, params, **kw)
            reqs = poisson_requests(
                rng, args.num_requests, args.arrival_rate, cfg.vocab,
                args.prompt_len, max(1, args.max_new_tokens // 2),
                args.max_new_tokens)
            for i, r in enumerate(eng.serve(reqs)):
                print(f"req {i} (t={r.arrival:.1f}): {r.out_tokens}")
            total = sum(len(r.out_tokens) for r in reqs)
            print(f"{total} tokens in {eng.decode_steps} decode steps "
                  f"({total / max(eng.decode_steps, 1):.2f} tok/step, "
                  f"{args.batch} slots)")
            return 0
        eng = ServingEngine(model, params, batch_slots=args.batch,
                            cache_cap=args.cache_cap)
        reqs = [Request(prompt=list(rng.integers(1, cfg.vocab,
                                                 args.prompt_len)),
                        max_new_tokens=args.max_new_tokens)
                for _ in range(args.batch)]
        frames = None
        if cfg.is_encoder_decoder:
            frames = rng.standard_normal(
                (args.batch, args.prompt_len, cfg.frontend_dim),
                dtype=np.float32)
        for i, r in enumerate(eng.serve(reqs, frames=frames)):
            print(f"req {i}: {r.out_tokens}")
        return 0

    cfg_b = get_config(args.colocate_with)
    if args.reduced:
        cfg_b = cfg_b.reduced()
    if args.experts is not None and cfg_b.moe is not None:
        import dataclasses
        cfg_b = dataclasses.replace(
            cfg_b, moe=dataclasses.replace(cfg_b.moe,
                                           n_experts=args.experts))
    model_b = Model(cfg_b)
    params_b = model_b.init(jax.random.PRNGKey(1))

    # Plan the expert pairing from synthetic routing statistics (§2.4:
    # historical traces drive the optimization).
    plan = planner = None
    if cfg.moe is not None and cfg_b.moe is not None and \
            cfg.moe.n_experts == cfg_b.moe.n_experts:
        from repro.core import AuroraPlanner, homogeneous_cluster, \
            synthetic_trace
        from repro.serving.colocated import apply_pairing
        n = cfg.moe.n_experts
        tr_a = synthetic_trace("a", n_experts=n, n_layers=2, seed=0)
        tr_b = synthetic_trace("b", n_experts=n, n_layers=2, seed=1)
        planner = AuroraPlanner(homogeneous_cluster(n))
        plan = planner.plan_colocated(tr_a, tr_b)
        params_b = apply_pairing(params_b, plan.pair, cfg_b)
        print(f"aurora colocation pairing: {plan.pair}")

    if args.arrival_rate is not None:
        replan = None
        if args.replan_interval is not None:
            if plan is None:
                raise SystemExit("--replan-interval needs two MoE models "
                                 "with equal expert counts")
            from repro.serving import OnlineReplanner
            replan = OnlineReplanner(planner, interval=args.replan_interval,
                                     threshold=args.replan_threshold,
                                     telemetry=telemetry)
        kw = dict(batch_slots=args.batch, cache_cap=args.cache_cap,
                  config=config, pair=(list(plan.pair) if plan else None),
                  replan=replan)
        if mesh is not None:
            from repro.serving import DistributedColocatedEngine
            eng = DistributedColocatedEngine(
                model, model_b, params, params_b, mesh=mesh,
                moe_impl=moe_impl, plan=plan, overlap=args.overlap,
                **kw)
            print(f"distributed EP colocation: {args.mesh}-device mesh, "
                  f"impl={moe_impl}, overlap={args.overlap}, "
                  f"{len(eng.rounds or ())} scheduled rounds")
        else:
            eng = ColocatedContinuousEngine(model, model_b, params, params_b,
                                            **kw)
        lo = max(1, args.max_new_tokens // 2)
        reqs_a = poisson_requests(rng, args.num_requests, args.arrival_rate,
                                  cfg.vocab, args.prompt_len, lo,
                                  args.max_new_tokens)
        reqs_b = poisson_requests(rng, args.num_requests, args.arrival_rate,
                                  cfg_b.vocab, args.prompt_len, lo,
                                  args.max_new_tokens)
        eng.serve(reqs_a, reqs_b)
        for tag, reqs in (("A", reqs_a), ("B", reqs_b)):
            total = sum(len(r.out_tokens) for r in reqs)
            print(f"model {tag}: {total} tokens over {len(reqs)} requests")
        print(f"{eng.decode_steps} lockstep decode steps")
        for e in eng.replan_events:
            tag = "APPLIED" if e.applied else "kept"
            print(f"replan @ step {e.step}: current {e.stale_time:.3f} vs "
                  f"candidate {e.candidate_time:.3f} -> {tag}")
        if eng.replan_events:
            print(f"final pairing: {eng.pair}")
        return 0

    eng = ColocatedEngine(model, model_b, params, params_b)
    pa = rng.integers(1, cfg.vocab, (args.batch, args.prompt_len))
    pb = rng.integers(1, cfg_b.vocab, (args.batch, args.prompt_len))
    out_a, out_b = eng.serve(pa, pb, max_new_tokens=args.max_new_tokens,
                             cache_cap=args.cache_cap)
    print("model A:", np.asarray(out_a).tolist())
    print("model B:", np.asarray(out_b).tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
