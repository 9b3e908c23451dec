"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the span of the benchmark's own ``bench.*`` annotations on
the host.  Device time is the union of the op intervals on each TPU plane's
``XLA Ops`` line (the TensorCore's ops; the DMA copies on ``Async XLA Ops``
overlap them), averaged over the chips used; idle gaps are the rest of the
window, each labelled with the innermost ``bench.*`` span the host was in
at the gap's middle.

The device's clock in the trace runs about a millisecond behind the
host's (on a v5e, the first program of a window shows as ending before the
call that launched it).  The device's ops are shifted so that the first
starts no earlier than the first host span; the labels of the gaps are
good to that shift, and the window's busy time does not depend on it.
The trace names each device op by its HLO text, so a Mosaic kernel call
is found by ``custom_call_target="tpu_custom_call"`` in its name.
"""

from __future__ import annotations

import glob
import math
import re
from collections import defaultdict
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
NO_SPAN = "outside bench spans"


@dataclass
class Reduction:
    window: tuple[int, int]                 # ns, host clock
    ops: list[list[tuple[int, int, str]]]   # per chip: (start, end, name)
    spans: list[tuple[int, int, str]]       # host bench spans
    busy: list[list[tuple[int, int]]] = field(default_factory=list)

    def __post_init__(self):
        self.busy = [_union(o) for o in self.ops]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        per_chip = [sum(e - s for s, e in b) / 1e9 for b in self.busy]
        return sum(per_chip) / len(per_chip)

    def op_seconds(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for chip in self.ops:
            for s, e, name in chip:
                out[name] += (e - s) / 1e9 / len(self.ops)
        return dict(out)

    def gaps(self) -> list[tuple[str, float]]:
        """Idle gaps of the first chip: (host span, seconds)."""
        out, t = [], self.window[0]
        for s, e in self.busy[0] + [(self.window[1], self.window[1])]:
            if s > t:
                out.append((self._label((t + s) // 2), (s - t) / 1e9))
            t = max(t, e)
        return out

    def _label(self, t: int) -> str:
        inside = [sp for sp in self.spans if sp[0] <= t < sp[1]]
        return max(inside)[2] if inside else NO_SPAN

    def top_ops(self, n: int) -> list[list]:
        """Device time per op, the longest first, by short name."""
        per: dict[str, float] = defaultdict(float)
        for name, secs in self.op_seconds().items():
            per[op_label(name)] += secs
        return [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> list[list]:
        """Idle time per host span, the longest first."""
        per: dict[str, float] = defaultdict(float)
        for label, secs in self.gaps():
            per[label] += secs
        return [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield start, start + int(ev.duration_ns), ev.name


def reduce_profile(profile, chips: int = 1) -> Reduction:
    """``profile``: a ``jax.profiler.ProfileData``."""
    spans, ops = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[2].startswith(SPAN_PREFIX)]
        elif plane.name.startswith("/device:TPU:") and len(ops) < chips:
            chip = [e for line in plane.lines if line.name == OPS_LINE
                    for e in _events(line)]
            if chip:
                ops.append(chip)
    if not spans:
        raise ValueError("the trace holds no bench.* span")
    if not ops:
        raise ValueError("the trace holds no device op")
    window = (min(s for s, _, _ in spans), max(e for _, e, _ in spans))
    shift = max(0, window[0] - min(s for chip in ops for s, _, _ in chip))
    clipped = [[(max(s + shift, window[0]), min(e + shift, window[1]), n)
                for s, e, n in chip
                if e + shift > window[0] and s + shift < window[1]]
               for chip in ops]
    return Reduction(window, clipped, sorted(spans))


_LABEL = re.compile(r"^%?([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def op_label(name: str) -> str:
    """``fusion.26 f32[1024,4096] fusion`` for an op's full HLO text."""
    m = _LABEL.match(name)
    return " ".join(m.groups()) if m else name.split(" = ")[0].lstrip("%")


_CALL = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]*)\]")


def kernel_calls(hlo_text: str) -> dict[str, int]:
    """Op name -> output elements of every Mosaic kernel call
    (``tpu_custom_call``) in HLO text, one instruction per line: a compiled
    program's, or the device ops' names of a trace."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _CALL.match(line)
            if m:
                dims = [int(d) for d in m.group(2).split(",") if d]
                out[m.group(1)] = math.prod(dims)
    return out


def reduce_dir(path: str, chips: int = 1) -> Reduction:
    import jax
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}, "
                         f"found {files}")
    return reduce_profile(jax.profiler.ProfileData.from_file(files[0]),
                          chips)
