#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload gpt2m-fwd --seeds 1,2,3 --seconds 2

For each seed, in one process: the program's own reading (a short window
at the cell's load, then the comparison a run makes), and the control's:
the reference computed one precision below the configuration's (``HIGH``,
three bf16 passes, for float32 at ``HIGHEST``) put in the program's place.
For a train cell, also the ``half_batch`` fault planted in the program.
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import faults  # noqa: E402
import harness  # noqa: E402
import refops  # noqa: E402

LOWER = {"highest": "high", "float32": "high"}


def window(driver, entry: str, seconds: float, seed: int):
    if entry == "serve":
        return driver.run(seconds)[1]
    if entry == "train":
        return []
    return harness.closed_loop(driver, seconds, seed)[1]


def readings(bench, cell: dict, seed: int, seconds: float,
             lower=None) -> dict:
    """The program's, the control's (``lower``: the contractions one
    precision below the configuration's) and, for training, the
    ``half_batch`` fault's readings on one seed."""
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    entry = traffic["entry"]
    lower = lower or refops.at(LOWER[cfg.spec["precision"]])
    driver = harness.DRIVERS[entry](cfg, traffic, seed)
    driver.setup()
    sample = window(driver, entry, seconds, seed)
    driver.free()
    out = {"seed": seed, "program": driver.check(sample)}
    if entry == "train":
        driver.losses, driver.m1, driver.p3, _ = driver.reference_steps(lower)
        out["control"] = driver.check(sample)
        with faults.half_batch():
            bad = harness.DRIVERS[entry](cfg, traffic, seed)
            bad.setup()
            bad.free()
            out["half_batch"] = bad.check([])
    else:
        ref = harness.reference_fn(cfg, "reference", lower)
        xs = driver.xs
        ctrl = [(slot, ref(driver.weights, *(xs[slot] if entry == "forward"
                                             else [xs[slot]])))
                for slot, _ in sample]
        out["control"] = driver.check(ctrl)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    root = HERE.parent
    harness.check_environment()
    bench = harness.Bench(root)
    cell = bench.cell(args.workload)
    harness.check_device(int(cell["chips"]))
    harness.import_program(root)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(bench, cell, seed, args.seconds)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
