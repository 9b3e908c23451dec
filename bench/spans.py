"""The program's own spans on the device trace's clock.

Reads the same profile as ``trace.py``.  On the host: the ``codo.*`` spans
(the program's, around each call, env assembly, dispatch, train phase and
host sync), the ``bench.*`` spans (the benchmark's, around each call into
the program and each wait), and two events of the TPU runtime that carry
the run's ``run_id``: ``DoEnqueueProgram`` (the host hands the run to the
device, inside the dispatch) and ``CompleteCallbacks`` (the host learns
that the run has ended).  On the first TPU plane: the ``XLA Modules`` line,
one event per run of a jitted program, named ``jit_<name>(<id>)`` and
carrying the same ``run_id``, and the ``XLA Ops`` line (busy time).

The device's clock is not the host's.  Each run of ``jit_codo_<P>`` is
paired by ``run_id`` with its enqueue and callbacks, and in order with the
``codo.dispatch.<P>`` spans: the k-th dispatch launched the k-th run (the
enqueue may come after the dispatch has returned, on another thread, but
never before it began).  The offset delta that takes device times to host
times is then bracketed by two facts:

* a run cannot start on the device before the host began to enqueue it:
  delta >= enqueue start - module start, for every run;
* the host cannot learn that a run has ended before it has:
  delta <= callbacks start - module end, for every run.

These are tighter forms of "a module cannot start before its dispatch
began" and "a wait cannot end before the module it waits on has ended":
the enqueue follows the dispatch's start, and the callbacks precede the
end of any wait on the run.  The two clocks also run at rates some parts
in a million apart (on a v5e about ten microseconds a second, more than
the tightest brackets of a two-second window), so the bracket is taken
per quarter second of device time and delta is the middle of its
quarter's bracket.  The widest of the brackets is the alignment's
uncertainty.  Where the trace holds no pairing or a bracket is empty,
there is no alignment, and the reason is printed.

On that clock each idle stretch of the device within the window (the span
of the ``bench.*`` spans, as in ``trace.py``) is split by the innermost
``codo.*`` or ``bench.*`` span the host was in.
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass

import trace as trace_mod

PREFIXES = ("codo.", "bench.")
DISPATCH = "codo.dispatch."
SYNC = "codo.sync"
ENQUEUE, DONE = "DoEnqueueProgram", "CompleteCallbacks"
MODULES_LINE = "XLA Modules"
PIECE_NS = 250_000_000  # device time over which delta is taken as constant
_MODULE = re.compile(r"^jit_codo_(\w+?)(?:\(|$)")


@dataclass
class Run:
    program: str
    dispatch: int       # ns, host clock: start of the codo.dispatch span
    start: int          # ns, device clock


@dataclass
class Aligned:
    window: tuple[int, int]             # ns, host clock
    spans: list[tuple[int, int, str]]   # host codo.* and bench.* spans
    busy: list[tuple[int, int]]         # device busy, host clock, in window
    brackets: list[tuple[int, int, int]]    # (device start, lo, hi), ns
    runs: list[Run]

    def delta(self, t: float) -> float:
        """Host time minus device time at device time ``t``, in ns: the
        middle of the bracket of the piece ``t`` falls in."""
        i = max(0, bisect.bisect_right([b[0] for b in self.brackets], t) - 1)
        return (self.brackets[i][1] + self.brackets[i][2]) / 2

    @property
    def width_us(self) -> float:
        return max(hi - lo for _, lo, hi in self.brackets) / 1e3

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def segments(self) -> list[tuple[int, int, str]]:
        """The host's timeline over the window as (start, end, innermost
        span); ``trace.NO_SPAN`` where the host was in none."""
        timed = [(i, s, e) for i, (s, e, _) in enumerate(self.spans) if e > s]
        # At one instant, spans end before others start; the outer of two
        # starts first and ends last.
        marks = sorted([(s, 1, -e, i) for i, s, e in timed]
                       + [(e, 0, -s, i) for i, s, e in timed])
        out, stack, t = [], [], self.window[0]
        for when, starts, _, i in marks:
            when = min(max(when, self.window[0]), self.window[1])
            if when > t:
                label = self.spans[stack[-1]][2] if stack else trace_mod.NO_SPAN
                out.append((t, when, label))
                t = when
            if starts:
                stack.append(i)
            else:
                stack.remove(i)
        if t < self.window[1]:
            out.append((t, self.window[1], trace_mod.NO_SPAN))
        return out

    def idle(self) -> list[tuple[int, int]]:
        out, t = [], self.window[0]
        for s, e in self.busy + [(self.window[1], self.window[1])]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        return out

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of the device per innermost host span."""
        per: dict[str, float] = defaultdict(float)
        idle = self.idle()
        j = 0
        for s, e, label in self.segments():
            while j < len(idle) and idle[j][1] <= s:
                j += 1
            k = j
            while k < len(idle) and idle[k][0] < e:
                per[label] += (min(e, idle[k][1]) - max(s, idle[k][0])) / 1e9
                k += 1
        return dict(per)

    def top_gaps(self, n: int) -> list[list]:
        per = self.idle_by_span()
        return [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def program_idle_share(self) -> float:
        """Percent of the window in which the device idles while the host
        runs the program's own code: inside a ``codo.*`` span other than
        ``codo.sync``."""
        idle = sum(v for k, v in self.idle_by_span().items() if _program(k))
        return 100.0 * idle / self.window_s

    def program_host_s(self) -> float:
        """Host seconds inside ``codo.*`` spans, less those in
        ``codo.sync``, where the host waits for the device."""
        return sum(e - s for s, e, label in self.segments()
                   if _program(label)) / 1e9

    def launch_us(self) -> dict[str, float]:
        """Mean time from the start of a dispatch to the start of its run
        on the device, per program, on the aligned clock."""
        per = defaultdict(list)
        for r in self.runs:
            per[r.program].append(r.start + self.delta(r.start) - r.dispatch)
        return {p: sum(v) / len(v) / 1e3 for p, v in sorted(per.items())}


def _program(label: str) -> bool:
    return label.startswith("codo.") and label != SYNC


def _run_id(ev) -> int | None:
    for key, value in ev.stats:
        if key == "run_id":
            return int(value)
    return None


def align(profile, log=sys.stderr) -> Aligned | None:
    """``profile``: a ``jax.profiler.ProfileData``.  None, with the reason
    on ``log``, where the trace holds no pairing or an empty bracket."""
    spans, ops = [], None
    host = {ENQUEUE: {}, DONE: {}}          # event -> run_id -> start
    modules = {}                            # run_id -> (start, end, program)
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
                    elif ev.name in host and _run_id(ev) is not None:
                        host[ev.name][_run_id(ev)] = int(ev.start_ns)
        elif plane.name.startswith("/device:TPU:") and ops is None:
            lines = {line.name: line for line in plane.lines}
            if trace_mod.OPS_LINE not in lines:
                continue
            ops = list(trace_mod._events(lines[trace_mod.OPS_LINE]))
            for ev in getattr(lines.get(MODULES_LINE), "events", ()):
                m = _MODULE.match(ev.name)
                if m and _run_id(ev) is not None:
                    s = int(ev.start_ns)
                    modules[_run_id(ev)] = (s, s + int(ev.duration_ns),
                                            m.group(1))
    spans.sort()
    bench = [sp for sp in spans if sp[2].startswith("bench.")]
    dispatches = [sp for sp in spans if sp[2].startswith(DISPATCH)]
    if not bench or not ops or not dispatches:
        print(f"no alignment: the trace holds {len(bench)} bench spans, "
              f"{len(ops or ())} device ops, {len(dispatches)} codo.dispatch "
              f"spans", file=log)
        return None
    # The k-th dispatch of a program launched its k-th run: run ids grow
    # in launch order.
    runs, bounds = [], []   # (device start, lower, upper or None) per run
    for prog in sorted({n[len(DISPATCH):] for _, _, n in dispatches}
                       | {m[2] for m in modules.values()}):
        starts = [s for s, _, n in dispatches if n == DISPATCH + prog]
        ids = sorted(r for r, m in modules.items() if m[2] == prog)
        if len(starts) != len(ids) or any(r not in host[ENQUEUE]
                                          for r in ids):
            print(f"no alignment: {prog} has {len(starts)} codo.dispatch "
                  f"spans, {len(ids)} device runs, "
                  f"{sum(r in host[ENQUEUE] for r in ids)} enqueues",
                  file=log)
            return None
        for ds, r in zip(starts, ids):
            ms, me, _ = modules[r]
            runs.append(Run(prog, ds, ms))
            if host[ENQUEUE][r] < ds:
                print(f"no alignment: run {r} of {prog} was enqueued before "
                      f"its dispatch began", file=log)
                return None
            bounds.append((ms, host[ENQUEUE][r] - ms,
                           host[DONE][r] - me if r in host[DONE] else None))
    t0 = min(t for t, _, _ in bounds)
    pieces = defaultdict(list)
    for t, lo, hi in bounds:
        pieces[(t - t0) // PIECE_NS].append((lo, hi))
    brackets = []
    for k, piece in sorted(pieces.items()):
        lo = max(low for low, _ in piece)
        hi = min((high for _, high in piece if high is not None), default=None)
        if hi is None or hi < lo:
            print(f"no alignment: the offset bracket [{lo}, {hi}] ns of "
                  f"piece {k} is empty: runs are paired wrongly", file=log)
            return None
        brackets.append((t0 + k * PIECE_NS, lo, hi))
    window = (bench[0][0], max(e for _, e, _ in bench))
    out = Aligned(window, spans, [], brackets, runs)
    for s, e in trace_mod._union(ops):
        s, e = s + out.delta(s), e + out.delta(e)
        if e > window[0] and s < window[1]:
            out.busy.append((max(int(s), window[0]), min(int(e), window[1])))
    print(f"device clock: offset {out.delta(t0) / 1e3:.3f} us to the "
          f"host's, bracket width {out.width_us:.3f} us over {len(runs)} "
          f"runs in {len(brackets)} pieces", file=log)
    return out

