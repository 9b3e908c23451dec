"""Benchmark harness — one function per paper table/figure.  Prints
``name,value,derived`` CSV and writes results/bench/*.csv; ``--json``
additionally collects every suite into one machine-readable document (what
the nightly CI job uploads).  Every suite prices with the cost model, so
its numbers are the same on any host.

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run --only table2,fig10
    PYTHONPATH=src python -m benchmarks.run --json results/bench/bench.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from benchmarks import tables  # noqa: E402

OUT = Path(__file__).resolve().parents[1] / "results" / "bench"

SUITES = {
    "table2": tables.table2_kernels,
    "table3": tables.table3_dnns,
    "table4": tables.table4_dnns,
    "gpt2": tables.gpt2_eval,
    "fig10": tables.ablation,
    "table7": tables.table7_batch,
    "fig11": tables.parallelism_sweep,
    "table8": tables.fifo_percentage,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help=f"comma list of {sorted(SUITES)}")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write every suite's rows to one JSON file")
    args = ap.parse_args(argv)
    wanted = None if args.only == "all" else set(args.only.split(","))

    OUT.mkdir(parents=True, exist_ok=True)
    report: dict[str, object] = {}
    print("name,value,derived")
    for name, fn in SUITES.items():
        if wanted is not None and name not in wanted:
            continue
        t0 = time.time()
        rows = fn()
        lines = [r.csv() for r in rows]
        (OUT / f"{name}.csv").write_text("name,value,derived\n"
                                         + "\n".join(lines) + "\n")
        for line in lines:
            print(line)
        elapsed = time.time() - t0
        print(f"{name}/elapsed_s,{elapsed:.2f},")
        report[name] = {
            "elapsed_s": round(elapsed, 3),
            "rows": [{"name": r.name, "value": r.value, "derived": r.derived}
                     for r in rows],
        }
    if args.json:
        path = Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
        print(f"wrote {path} ({len(report)} suites)", file=sys.stderr)


if __name__ == "__main__":
    main()
