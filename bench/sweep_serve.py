#!/usr/bin/env python3
"""Sweep the offered rate of a served cell once, to find its knee.

    python3 bench/sweep_serve.py --workload gpt2m-serve --rates 50,100,200 --seconds 8

One process, one set-up; for each rate an open-loop window as the cell's
runs make, then: offered and completed requests per second, p50 and p95
latency, and p95 of the window's first and second halves (a queue that
grows through the window shows as a second half far above the first).
The knee is the highest rate whose completions keep up and whose halves
agree; the cell's traffic file then fixes its rate at 0.8 of it.  The
benchmark's own runs never run this.
"""

import argparse
import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    root = HERE.parent
    harness.check_environment()
    bench = harness.Bench(root)
    cell = bench.cell(args.workload)
    harness.check_device(int(cell["chips"]))
    harness.import_program(root)
    traffic = bench.traffic(cell["traffic"])
    driver = harness.Serve(bench.config(cell["config"]), traffic, args.seed)
    driver.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        lat, _, late = driver.run(args.seconds)
        done = [x for x in lat if math.isfinite(x)]
        half = len(lat) // 2
        first = [x for x in lat[:half] if math.isfinite(x)] or [math.inf]
        second = [x for x in lat[half:] if math.isfinite(x)] or [math.inf]
        print(json.dumps({
            "rate_per_s": rate, "offered_per_s": len(lat) / args.seconds,
            "completed_per_s": len(done) / driver.window_s,
            "p50_ms": stats.percentile(done, .5) * 1e3 if done else None,
            "p95_ms": stats.percentile(lat, .95) * 1e3,
            "p95_first_half_ms": stats.percentile(first, .95) * 1e3,
            "p95_second_half_ms": stats.percentile(second, .95) * 1e3,
            "late_p95_ms": stats.percentile(late, .95) * 1e3,
            "batches_requests": driver.batches()}), flush=True)
    driver.free()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
