"""Operations and bytes of a graph or of a kernel's chain, from the graph's
buffer shapes and dtypes alone.

Only contractions count as operations (matmul, matrix-vector, conv): they
are what the MXU peak in ``peaks.json`` is a peak of.  A chain's bytes are
the least it must move through HBM: every buffer it reads that no task of
the chain writes, once, and what its last task writes, once.  Both are
properties of the work, not of the code that does it, so a kernel's
roofline share reads the same work whatever implements the chain.
"""

from __future__ import annotations

import math


def _shape(graph, name: str) -> tuple[int, ...]:
    return tuple(int(s) for s in graph.buffers[name].shape)


def _nbytes(graph, name: str) -> int:
    import numpy as np
    buf = graph.buffers[name]
    return math.prod(_shape(graph, name)) * np.dtype(buf.dtype).itemsize


def spec_flops(graph, spec) -> int:
    """Multiply-add operations (2 per MAC) of one op spec."""
    if spec.parts:
        return sum(spec_flops(graph, p) for p in spec.parts)
    if spec.kind == "matmul":
        k = _shape(graph, spec.ins[0])[-1]
        return 2 * math.prod(_shape(graph, spec.outs[0])) * k
    if spec.kind == "conv2d":
        w = _shape(graph, spec.ins[1])
        return 2 * math.prod(_shape(graph, spec.outs[0])) * math.prod(w[1:])
    if spec.kind == "mv":
        return 2 * math.prod(_shape(graph, spec.ins[0]))
    return 0


def graph_flops(graph) -> int:
    return sum(spec_flops(graph, t.spec) for t in graph.tasks
               if t.spec is not None)


def chain_work(graph, task_names) -> tuple[int, int]:
    """(flops, bytes) of one routed chain of tasks, in dataflow order."""
    tasks = [graph.task(n) for n in task_names]
    written = {a.buffer for t in tasks for a in t.writes}
    read = []
    for t in tasks:
        for a in t.reads:
            if a.buffer not in written and a.buffer not in read:
                read.append(a.buffer)
    out = {a.buffer for a in tasks[-1].writes}
    flops = sum(spec_flops(graph, t.spec) for t in tasks if t.spec is not None)
    nbytes = sum(_nbytes(graph, b) for b in read) + sum(_nbytes(graph, b)
                                                        for b in out)
    return flops, nbytes


def chain_out_elems(graph, task_names) -> int:
    """Elements the chain's last task writes: what identifies its call
    among the compiled program's kernels (layouts may transpose it)."""
    last = graph.task(task_names[-1])
    return sum(math.prod(_shape(graph, a.buffer)) for a in last.writes)


def roofline_share(family: str, routes, calls: dict, events, peak: dict):
    """Percent of the least time over the device time of ``family``'s
    kernel calls in the trace.

    ``routes``: (kernel, flops, bytes, out elements) of every routed chain;
    ``calls``: the kernel calls, HLO name -> out elements;
    ``events``: (start ns, end ns, HLO text) of the device ops.  A
    call is ``family``'s when its output size matches one of the family's
    chains and no other routed chain's.  None when no call is found."""
    least, other = {}, set()
    for kernel, flops, nbytes, elems in routes:
        if kernel.startswith(family):
            least.setdefault(elems, set()).add(
                least_seconds(flops, nbytes, peak)[0])
        else:
            other.add(elems)
    names = {name: next(iter(least[n])) for name, n in calls.items()
             if n in least and n not in other and len(least[n]) == 1}
    hits = [(e - s, names[n]) for s, e, n in
            ((s, e, name.split(" = ", 1)[0].lstrip("%"))
             for s, e, name in events) if n in names]
    if not hits:
        return None
    return 100.0 * sum(t for _, t in hits) / (sum(d for d, _ in hits) / 1e9)


def least_seconds(flops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The roofline's least time for this work on a chip, and which side
    bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
