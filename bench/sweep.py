#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains: one run per
rate (no check), each printing the end-to-end metrics and whether the
backlog grew across the window (TTFT of the requests due in its last
third against its first third, and requests still waiting for their
first token when it closed).

  python3 bench/sweep.py --workload <name> --rates 2.4,2.8,3.2 \
      --seconds 45
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, measure  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    t0 = T_PROC0
    for rate in [float(r) for r in args.rates.split(",")]:
        spec = harness.load_spec(args.workload)
        spec.mix["rate_per_s"] = rate
        res = harness.run_cell(spec, args.seed, args.seconds, False,
                               t_proc0=t0, score=False)
        run = res["_run"]
        third = (run.w1 - run.w0) / 3
        first = measure.ttfts(run.records, run.w0, run.w0 + third)
        last = measure.ttfts(run.records, run.w1 - third, run.w1)
        waiting = sum(1 for r in run.records if run.w0 <= r.due < run.w1
                      and not (r.times and r.times[0] < run.w1))
        print(json.dumps({
            "rate_per_s": rate, "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "ttft_p50_ms_first_third": 1e3 * (measure.percentile(first, 50) or 0),
            "ttft_p50_ms_last_third": 1e3 * (measure.percentile(last, 50) or 0),
            "waiting_at_close": waiting}), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
