import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell this lowers + compiles the full
production program on BOTH meshes:

    single-pod:  (16, 16)      = ("data", "model")        256 chips
    multi-pod:   (2, 16, 16)   = ("pod", "data", "model") 512 chips

and records ``memory_analysis()`` (proof of HBM fit) and
``cost_analysis()`` + parsed collective bytes (for §Roofline). The full
compile runs the SCANNED stacks (O(1) HLO in depth); exact FLOP/byte totals
come from the roofline prober (launch/roofline.py) on the single-pod mesh.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    python -m repro.launch.dryrun --all [--out experiments/dryrun]
    python -m repro.launch.dryrun --all --skip-probes   # compile-only pass
"""
import argparse
import json
import time
import traceback

import jax

from ..configs import ALL_SHAPES, ARCHS, get_config, get_shape, shape_applicable
from . import cells as C
from . import roofline as R
from .mesh import make_production_mesh


def memory_dict(ma) -> dict:
    return {
        k: int(getattr(ma, k))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
        )
    }


def run_cell(arch: str, shape_name: str, *, probes: bool = True,
             dispatch_mode: str = "staged") -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "reason": why}

    rec: dict = {"arch": arch, "shape": shape_name, "status": "ok",
                 "dispatch_mode": dispatch_mode if cfg.n_experts else None}
    for mesh_kind, multi_pod in (("single_pod", False), ("multi_pod", True)):
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.time()
        step, args, meta = C.build_cell(cfg, shape, mesh,
                                        dispatch_mode=dispatch_mode)
        args = tuple(a for a in args if a is not None)
        with mesh, jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            lowered = jax.jit(step).lower(*args)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        rec[mesh_kind] = {
            "compile_s": round(time.time() - t0, 1),
            "memory": memory_dict(ma),
            "per_device_gb": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
            **meta,
        }
        # raw (scan-body-once) cost numbers for reference; exact totals come
        # from the probes below
        rec[mesh_kind]["cost_raw"] = {
            k: float(v) for k, v in compiled.cost_analysis().items()
            if k in ("flops", "bytes accessed")
        }
        rec[mesh_kind]["collectives_raw"] = R.collective_bytes(compiled.as_text())

    if probes:
        mesh = make_production_mesh(multi_pod=False)
        t0 = time.time()
        metrics = R.probe_cell(cfg, shape, mesh, dispatch_mode=dispatch_mode)
        rec["probe_s"] = round(time.time() - t0, 1)
        rec["metrics"] = metrics
        rec["roofline"] = R.roofline_terms(metrics, cfg, shape)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--dispatch-mode", default="staged",
                    choices=("direct", "staged", "adaptive"))
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true",
                    help="resume a sweep: skip cells with an ok/skipped JSON")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in ALL_SHAPES:
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                old = json.load(f)
            if old.get("status") in ("ok", "skipped"):
                print(f"[cached ] {tag}", flush=True)
                continue
        try:
            rec = run_cell(arch, shape, probes=not args.skip_probes,
                           dispatch_mode=args.dispatch_mode)
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc(limit=8)}
            failures += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f" mem/dev {rec['single_pod']['per_device_gb']}GB"
                     f" compile {rec['single_pod']['compile_s']}s"
                     f"+{rec['multi_pod']['compile_s']}s")
            if "roofline" in rec:
                r = rec["roofline"]
                extra += (f" | compute {r['compute_s']*1e3:.2f}ms"
                          f" mem {r['memory_s']*1e3:.2f}ms"
                          f" coll {r['collective_s']*1e3:.2f}ms"
                          f" -> {r['dominant']}")
        elif status == "skipped":
            extra = " " + rec["reason"][:60]
        else:
            extra = " " + rec["error"][:90]
        print(f"[{status:7s}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
