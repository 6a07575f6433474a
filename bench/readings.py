#!/usr/bin/env python3
"""Correctness readings of one cell over many seeds, in one process: for
each seed a whole run (set-up, window, check) whose check also scores
the fp8 control on the same requests.  One JSON line per seed: the
program's readings (`gap`, `logprob_err`) and `correct`; the control's
readings (`ctrl_gap`, `ctrl_logprob_err`) and `ctrl_correct`, the
harness's own comparison (`harness.is_correct`) applied to them with the
cell's limits, which has to come out false; and the run's end-to-end
metrics.  The limits in `bench/cells/<cell>.json` are set from these
readings; the benchmark's own runs never score the control.

  python3 bench/readings.py --workload <name> --seeds 1,2,3 --seconds 45
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = harness.load_spec(args.workload)
    t0 = T_PROC0
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(spec, seed, args.seconds, False, t_proc0=t0,
                               control=True)
        ctrl = harness.control_readings(res["readings"])
        limits = spec.cell["check"]["limits"]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "ctrl_correct": harness.is_correct(ctrl, limits),
                          "check": res["check"],
                          "ctrl_check": harness.numbers(ctrl, limits),
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "readings": res["readings"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
