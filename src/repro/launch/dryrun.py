"""Multi-pod dry-run (deliverable e).

Lowers + compiles the appropriate step for every supported
(architecture × input-shape) pair on the production meshes:

  16×16      (data, model)        — 256 chips, one pod
  2×16×16    (pod, data, model)   — 512 chips, two pods

and records ``memory_analysis()`` (fits-in-HBM evidence),
``cost_analysis()`` (FLOPs/bytes for §Roofline), and the collective-byte
histogram parsed from the compiled HLO. Failures here (sharding mismatch,
unsupported collective) are bugs in the system.

``main`` splits the host platform into 512 devices before jax initializes
(jax locks the device count at first init), so run it as its own process:
``PYTHONPATH=src python -m repro.launch.dryrun --arch ... --shape ...``.

Usage:
  python -m repro.launch.dryrun --arch deepseek-v3-671b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import json
import os
import time
import traceback


def run_one(arch: str, shape_name: str, multi_pod: bool,
            moe_impl: str = "ep", out_dir: str | None = None,
            calibrate: bool = True) -> dict:
    import jax
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch import specs as S
    from repro.roofline.analysis import (collective_bytes_from_hlo,
                                         roofline_report)
    from repro.roofline.calibrate import calibrated_cost

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "moe_impl": moe_impl}
    if not S.supported(cfg, shape):
        rec["status"] = "skipped (shape-skip matrix, see DESIGN.md)"
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        step_fn, args = S.lowering_args(cfg, shape, mesh, moe_impl=moe_impl)
        # Donation: train aliases params+opt in place, serving aliases the
        # KV/SSM cache — no full-state copy per step (§Perf iteration 1).
        donate = (0, 1) if shape.kind == "train" else (2,)
        lowered = jax.jit(step_fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_dev = mesh.devices.size
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        n_devices=int(n_dev),
    )
    if mem is not None:
        # memory_analysis reports PER-DEVICE sizes for the SPMD program.
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
        args_b = rec["memory"].get("argument_size_in_bytes", 0)
        temp_b = rec["memory"].get("temp_size_in_bytes", 0)
        rec["memory"]["per_device_total_gib"] = round(
            (args_b + temp_b) / 2**30, 3)
    raw_cost = {k: float(v) for k, v in (cost or {}).items()
                if k in ("flops", "bytes accessed")}
    rec["cost_raw"] = dict(raw_cost,
                           note="per-device; scan bodies counted ONCE")
    coll_raw = collective_bytes_from_hlo(compiled.as_text())
    rec["collectives_raw"] = coll_raw

    if calibrate:
        # Scan-corrected per-device cost (see roofline/calibrate.py).
        cal = calibrated_cost(cfg, shape, mesh, moe_impl=moe_impl)
        rec["cost"] = {"flops": cal["flops"], "bytes": cal["bytes"],
                       "collective_bytes": cal["collective_bytes"]}
        rec["calibration"] = cal["detail"]
        flops, hbm, coll_b = (cal["flops"], cal["bytes"],
                              cal["collective_bytes"])
    else:
        flops = raw_cost.get("flops", 0.0)
        hbm = raw_cost.get("bytes accessed", 0.0)
        coll_b = coll_raw["link_bytes"]
    rec["roofline"] = roofline_report(
        flops=flops, hbm_bytes=hbm, collective_bytes=coll_b,
        n_devices=int(n_dev), cfg=cfg, shape=shape,
        arg_bytes=rec.get("memory", {}).get("argument_size_in_bytes"),
        out_bytes=rec.get("memory", {}).get("output_size_in_bytes"))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    from repro.launch.mesh import force_host_device_count
    force_host_device_count(512)
    from repro.configs import ARCH_IDS, SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-impl", default="ep", choices=["ep", "aurora"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the scan-correction calibration lowerings")
    args = ap.parse_args()

    combos = []
    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = 0
    for arch, shape, mp in combos:
        tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
        try:
            # The roofline table (§Roofline) is single-pod only; multi-pod
            # runs prove sharding coherence + memory, skipping calibration.
            rec = run_one(arch, shape, mp, moe_impl=args.moe_impl,
                          out_dir=args.out,
                          calibrate=not args.no_calibrate and not mp)
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (f"  lower {rec['lower_s']}s compile "
                         f"{rec['compile_s']}s "
                         f"mem/dev {rec.get('memory', {}).get('per_device_total_gib', '?')} GiB")
            print(f"[dryrun] {tag}: {status}{extra}", flush=True)
        except Exception:
            failures += 1
            print(f"[dryrun] {tag}: FAILED", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
