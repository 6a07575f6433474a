"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and
appends each run's rows to per-prefix trajectory artifacts
(``BENCH_kernels.json``, ``BENCH_serving.json``) so kernel and serving
perf have a machine-readable history across commits — the CI bench job
uploads them and gates on ``benchmarks/check_regression.py``.
"""
import json
import pathlib
import sys
import time
import traceback

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
# row-name prefix -> committed trajectory artifact
ARTIFACTS = {
    "kernels/": REPO_ROOT / "BENCH_kernels.json",
    "serving/": REPO_ROOT / "BENCH_serving.json",
}
BENCH_KERNELS_PATH = ARTIFACTS["kernels/"]


def _write_artifacts():
    from benchmarks import common
    stamp = time.strftime("%Y-%m-%dT%H:%M:%S")
    for prefix, path in ARTIFACTS.items():
        rows = [r for r in common.RECORDS if r["name"].startswith(prefix)]
        if not rows:
            continue
        runs = []
        if path.exists():
            try:
                runs = json.loads(path.read_text())
            except (json.JSONDecodeError, OSError):
                runs = []
        runs.append({"timestamp": stamp, "rows": rows})
        path.write_text(json.dumps(runs, indent=2) + "\n")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        cost_analysis, fig5_reliability, fig12_throughput, fig13_breakdown,
        fig14_ablation, fig15_dse, fig16_energy, kernels_bench,
        serving_bench,
    )
    print("name,us_per_call,derived")
    modules = [
        ("fig12", fig12_throughput), ("fig13", fig13_breakdown),
        ("fig14", fig14_ablation), ("fig15", fig15_dse),
        ("fig16", fig16_energy), ("fig5", fig5_reliability),
        ("cost", cost_analysis), ("kernels", kernels_bench),
        ("serving", serving_bench),
    ]
    failed = []
    for name, mod in modules:
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}",
                  file=sys.stderr)
            traceback.print_exc()
    _write_artifacts()
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
