import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The first two lines above MUST precede any other import (jax locks the
device count at first init) — 512 host devices stand in for the production
fleet so `make_production_mesh` builds 16×16 and 2×16×16 meshes.

For each cell:  jit(step).lower(*ShapeDtypeStructs).compile()  — no array
is ever allocated.  Prints memory_analysis (fits?) + cost_analysis (FLOPs/
bytes) and derives the three roofline terms (launch/roofline.py), writing
one JSON artifact per cell under artifacts/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --arch-filter moe
"""
import argparse          # noqa: E402
import json              # noqa: E402
import signal            # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, \
    shape_applicable  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_cell  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             eng_overrides=None, verbose: bool = True,
             cell_timeout: int = 0):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    tag = f"{arch} × {shape_name} × {'2x16x16' if multi_pod else '16x16'}"
    if not ok:
        if verbose:
            print(f"SKIP {tag}: {why}")
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
              "chips": mesh.size}
    try:
        if cell_timeout:
            def _on_alarm(signum, frame):
                raise TimeoutError(f"cell exceeded {cell_timeout}s")
            signal.signal(signal.SIGALRM, _on_alarm)
            signal.alarm(cell_timeout)
        with jax.set_mesh(mesh):
            cell = build_cell(arch, shape_name, mesh, multi_pod=multi_pod,
                              eng_overrides=eng_overrides)
            lowered = cell.jitted.lower(*cell.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = rl.memory_summary(compiled)          # proves it fits
        mf = rl.model_flops_estimate(cfg, shape)
        roof = rl.analyze(compiled, mesh.size, mf, cell.fusible_last2)
        record.update(
            status="ok", note=cell.note,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory=mem, roofline=roof.to_dict(),
            bytes_per_device=mem.get("total_bytes"),
        )
        if verbose:
            print(f"OK   {tag}  [{cell.note}]")
            print(f"     mem/device: {mem.get('total_bytes', 0)/2**30:.2f} "
                  f"GiB (args {mem.get('argument_bytes', 0)/2**30:.2f} + "
                  f"temp {mem.get('temp_bytes', 0)/2**30:.2f})")
            print(f"     roofline: compute {roof.compute_s*1e3:.2f} ms | "
                  f"memory {roof.memory_s*1e3:.2f} ms (raw "
                  f"{roof.memory_raw_s*1e3:.2f}) | collective "
                  f"{roof.collective_s*1e3:.2f} ms -> {roof.bottleneck}"
                  f" | useful {roof.useful_ratio:.2f}")
    except BaseException as e:  # noqa: BLE001  (incl. TimeoutError)
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc())
        if verbose:
            print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            raise
    finally:
        signal.alarm(0)
    return record


def save_record(record):
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    name = (f"{record['arch']}__{record['shape']}__"
            f"{'multi' if record['multi_pod'] else 'single'}.json")
    with open(os.path.join(ARTIFACT_DIR, name), "w") as f:
        json.dump(record, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--arch-filter", default=None,
                    help="substring or family filter")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--quant", default=None, choices=["w8a8", "w4a16"])
    ap.add_argument("--variant", default=None,
                    choices=["compact", "discrete"])
    ap.add_argument("--page-tokens", type=int, default=None)
    ap.add_argument("--cell-timeout", type=int, default=1800)
    args = ap.parse_args()

    overrides = {}
    if args.quant:
        overrides["quant"] = args.quant
    if args.variant:
        overrides["variant"] = args.variant
    if args.page_tokens:
        overrides["page_tokens"] = args.page_tokens

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    if args.arch_filter:
        archs = [a for a in archs
                 if args.arch_filter in a
                 or get_config(a).family == args.arch_filter]
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = []
    if not args.multi_pod_only:
        pods.append(False)
    if not args.single_pod_only:
        pods.append(True)

    results = []
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                rec = run_cell(arch, shape_name, mp,
                               eng_overrides=overrides or None,
                               cell_timeout=args.cell_timeout)
                save_record(rec)
                results.append(rec)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_err} errors ===")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
