#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload gpt2m-fwd --seed 7 --seconds 10 --trace 0

From the root of a checkout.  ``--trace 0`` measures the cell's end-to-end
metrics over a window of ``--seconds``; ``--trace 1`` is a run of its own
that profiles a short window and reports the per-layer metrics, with the
device's busy time and a breakdown.  Every run checks what its window
produced against the configuration's plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, then ``checks``: each number compared with its limit).
Without a TPU, with fewer chips than the cell asks for, with a routing
switch set or outside a checkout of the program, the run exits 2 and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        result = harness.run_cell(HERE.parent, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except (harness.Refused, FileNotFoundError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
