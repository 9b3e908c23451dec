#!/usr/bin/env python3
"""Profile a few steps of one cell and reduce them on the device's clock.

    python3 bench/record_trace.py --workload gpt2m-fwd --seed 7 --steps 3 \\
        --out bench/tests/data/gpt2m-fwd-codo-3-steps.trace.json

From the root of a checkout, on the chip, as ``run.py`` runs: set-up and
warm-up as in a run of the cell, then ``--steps`` steps (or ``--seconds``
of steps) under the profiler.  Prints one JSON object: the window's steps
and per-step time, what the readers of ``READERS`` make of it, ``trace.py``'s
busy time and idle gaps, the aligned reduction of ``spans.py`` (offset
bracket, idle per innermost span, launch latency per program), the
program's dispatch and trace counts over the window, and the names of the
device's modules and kernel calls.  ``--out`` writes the profile
trimmed to what ``trace.py`` and ``spans.py`` read, for their tests;
``--keep DIR`` copies the raw ``.xplane.pb`` there.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

# The per-layer readers (``metrics/<name>.py``) this window feeds.
READERS = ("device_idle_share", "program_idle_share", "program_host_ms",
           "dispatches_per_step", "window_traces")


def counters() -> dict:
    """Sums of the program's per-program counters; empty where the
    program keeps none."""
    from repro.core.lowering import LOWER_CACHE_STATS
    return {k: sum(LOWER_CACHE_STATS[k].values())
            for k in ("dispatches", "traces") if k in LOWER_CACHE_STATS}


def trimmed(profile, recorded: str) -> dict:
    """What ``trace.py`` and ``spans.py`` read: the host's ``codo.*`` and
    ``bench.*`` spans and the runtime's enqueue and callback events, the
    first TPU plane's module and op lines; events as [name, start_ns,
    duration_ns] with the ``run_id`` appended where the event has one."""
    import spans
    import trace as trace_mod

    def row(ev):
        r = spans._run_id(ev)
        return ([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                + ([] if r is None else [r]))

    planes, device = [], False
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            lines = [(line.name, [row(ev) for ev in line.events
                                  if ev.name.startswith(spans.PREFIXES)
                                  or ev.name in (spans.ENQUEUE, spans.DONE)])
                     for line in plane.lines]
        elif plane.name.startswith("/device:TPU:") and not device:
            lines = [(line.name, [row(ev) for ev in line.events])
                     for line in plane.lines
                     if line.name in (spans.MODULES_LINE, trace_mod.OPS_LINE)]
            device = bool(lines)
        else:
            continue
        lines = [{"name": n, "events": evs} for n, evs in lines if evs]
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"recorded": recorded, "planes": planes}


def record(root: Path, workload: str, seed: int, steps: int,
           seconds: float, out: str | None, keep: str | None) -> dict:
    import harness
    import spans
    import trace as trace_mod

    import jax
    harness.check_environment()
    bench = harness.Bench(root)
    cell = bench.cell(workload)
    devs = harness.check_device(int(cell["chips"]))
    harness.import_program(root)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if traffic["entry"] == "serve":
        raise harness.Refused("record_trace drives closed-loop cells only")
    driver = harness.DRIVERS[traffic["entry"]](cfg, traffic, seed)
    driver.setup()
    print(f"set-up {time.perf_counter() - T_START:.3f} s", file=sys.stderr)

    tdir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        c0 = counters()
        jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        if steps:
            for i in range(steps):
                driver.step(i)
            n = steps
        else:
            _, _, n, _, _ = harness.closed_loop(driver, seconds, seed)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        c1 = counters()
        (path,) = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(keep) / f"{workload}.xplane.pb")
        profile = jax.profiler.ProfileData.from_file(path)
        red = trace_mod.reduce_profile(profile)
        al = spans.align(profile)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    driver.free()

    dev = devs[0]
    modules = sorted({ev.name.split("(")[0] for p in profile.planes
                      if p.name == "/device:TPU:0" for line in p.lines
                      if line.name == spans.MODULES_LINE
                      for ev in line.events})
    calls = sorted(trace_mod.kernel_calls(
        "\n".join({n for _, _, n in red.ops[0]})))
    ctx = {"trace": red, "program": al, "steps": n,
           "counters": {k: c1[k] - c0[k] for k in c1}}
    result = {
        "workload": workload, "seed": seed, "steps": n,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "step_ms_traced": wall / n * 1e3,
        "metrics": {m: bench.metric(m).read(ctx) for m in READERS},
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_gaps": red.top_gaps(10), "device_ops": red.top_ops(10),
        "modules": modules, "kernel_calls": calls,
        "counters": ctx["counters"],
    }
    if al is not None:
        result.update({"bracket_width_us": al.width_us,
                       "aligned_idle_gaps": al.top_gaps(10),
                       "launch_us": al.launch_us()})
    if out:
        doc = trimmed(profile, f"{dev.device_kind}, one chip, jax "
                      f"{jax.__version__}: {n} steps of {workload} (seed "
                      f"{seed}) as bench/record_trace.py drives them; host "
                      f"codo.*/bench.* spans, the runtime's DoEnqueueProgram "
                      f"and CompleteCallbacks, and the device's XLA Modules "
                      f"and XLA Ops lines, events as [name, start_ns, "
                      f"duration_ns(, run_id)]")
        Path(out).write_text(json.dumps(doc))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--steps", type=int, default=0)
    group.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", help="write the trimmed profile here")
    ap.add_argument("--keep", help="copy the raw .xplane.pb into this "
                                   "directory")
    args = ap.parse_args(argv)
    import harness
    try:
        result = record(HERE.parent, args.workload, args.seed, args.steps,
                        args.seconds, args.out, args.keep)
    except (harness.Refused, FileNotFoundError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
