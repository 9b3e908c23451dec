"""Lowering: a :class:`CompiledDataflow` → an executable JAX callable.

FIFO edges become *fusion groups*: maximal chains of FIFO-connected tasks
are executed as one fused function whose intermediates never round-trip
through HBM.  Which implementation runs a group is a *routing* decision
(:mod:`repro.core.routing`): producer→consumer chains matching a
registered :class:`~repro.core.routing.KernelPattern` — the motivating
pad→conv→relu chain, matmul→\\*ewise→matmul chains, softmax·matmul
attention tails — execute as hand-written Pallas streaming kernels; the
rest composes the tasks' jnp fns and lets XLA fuse inside jit.  Ping-pong
edges are group boundaries — the intermediate materializes in HBM,
double-buffered by the consumer's grid pipeline.  ``CODO_DISABLE_PALLAS=1``
turns all routing off.

This file is the analogue of the paper's HLS-C++ code generation (§VII-C);
functional equivalence against the un-optimized program is checked the
same way the paper's testbench does — by executing both and comparing.

Lowering results are memoized like compiles: keyed on the compiled graph's
``structural_hash()`` — which covers the fusion decisions (buffer impls,
fused-group ids) — plus the lowering flags, the routing switches
(``CODO_DISABLE_PALLAS`` and the kernel-pattern registry epoch), and the
op-registry epoch.  Re-lowering a structurally identical design (e.g. a
disk-cache hit in a fresh ``CompiledDataflow``) reuses the already-built
(and, under jit, the already-traced) program; flipping any routing switch
changes the key, so a toggle never serves a stale program.  The same
content-addressing contract as the compile cache applies: graphs with
equal structural hashes must have equal numerics (automatic for
spec-carrying tasks, the ``const:`` tag convention for closure-built ones).
"""

from __future__ import annotations

import os
import re
import threading
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .compiler import CompiledDataflow
from .costmodel import device_hw
from .graph import FIFO, DataflowGraph, GraphError, Task, task_region
from .ops import registry_epoch as _ops_epoch
from .routing import (XLA_FUSED, KernelPattern, RoutedKernel,
                      ensure_kernel_patterns, pallas_interpret_forced,
                      register_kernel_pattern, route_groups,
                      routing_state_key)

def register_group_kernel(pattern: tuple[str, ...],
                          factory: Callable[..., Callable]) -> None:
    """Legacy exact-op registration (pre-routing API): ``pattern`` is the
    full op tuple of a group and ``factory(graph, group)`` builds the
    step.  Kept as a shim over :func:`repro.core.routing.
    register_kernel_pattern`; new kernels should register a
    :class:`~repro.core.routing.KernelPattern` directly."""
    def adapter(graph, group, tasks):
        # Old factories index group.tasks positionally, assuming the match
        # covers the whole group; hand them a group view of just the chain.
        chain = FusionGroup(group.gid, [t.name for t in tasks],
                            tuple(t.op for t in tasks))
        return factory(graph, chain)

    register_kernel_pattern(KernelPattern(
        name="+".join(pattern), pattern=tuple(pattern), factory=adapter,
        description="legacy exact-op registration"))


@dataclass
class FusionGroup:
    gid: int
    tasks: list[str]
    ops: tuple[str, ...]
    kernel: str = XLA_FUSED       # or "pallas:<pattern>[+<pattern>...]"
    routes: list[RoutedKernel] = field(default_factory=list)
    # Cost-gate record (ISSUE 6): structural matches the gate turned down,
    # the group-level decision, and predicted cycles both ways.
    rejected: list[RoutedKernel] = field(default_factory=list)
    decision: str = "generic"     # "routed" | "generic" | "disabled"
    predicted_routed_cycles: float = 0.0
    predicted_generic_cycles: float = 0.0


@dataclass
class LoweredProgram:
    graph: DataflowGraph
    groups: list[FusionGroup]
    fn: Callable[[dict], dict]          # jitted: env(inputs+weights) -> outputs
    materialized: list[str] = field(default_factory=list)   # HBM intermediates
    # The jitted function's name, ``codo_<graph>``: the device trace shows
    # the program as ``jit_<name>``, and the counters key on it.
    name: str = "codo_program"
    # Conv tasks run as patch matmuls (``g<gid>.patchconv`` steps).
    patch_convs: int = 0

    def __post_init__(self):
        self.span = "codo.dispatch." + self.name.removeprefix("codo_")

    def __call__(self, env: dict[str, Any]) -> dict[str, Any]:
        with _COUNT_LOCK:
            LOWER_CACHE_STATS["dispatches"][self.name] += 1
        with TraceAnnotation(self.span):
            return self.fn(env)

    @property
    def routed_groups(self) -> list[FusionGroup]:
        return [g for g in self.groups if g.routes]

    def summary(self) -> str:
        return (f"lowered {self.graph.name}: {len(self.groups)} fusion groups "
                f"({sum(len(g.tasks) for g in self.groups)} tasks, "
                f"{len(self.routed_groups)} pallas-routed, "
                f"{self.patch_convs} patch-matmul convs), "
                f"{len(self.materialized)} HBM intermediates")


def fusion_groups(graph: DataflowGraph, impl: dict[str, str]) -> list[FusionGroup]:
    """Union tasks across FIFO edges (single-producer-single-consumer by
    construction after the coarse pass).  Routing (which kernel runs each
    group) is a separate decision — see :func:`repro.core.routing.
    route_groups`."""
    parent: dict[str, str] = {t.name: t.name for t in graph.tasks}

    def find(x: str) -> str:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a: str, b: str) -> None:
        parent[find(a)] = find(b)

    for p, buf, c in graph.internal_edges():
        if impl.get(buf) == FIFO:
            union(p.name, c.name)

    order = [t.name for t in graph.toposort()]
    by_root: dict[str, list[str]] = {}
    for n in order:
        by_root.setdefault(find(n), []).append(n)
    groups = []
    for gid, (_root, names) in enumerate(
            sorted(by_root.items(), key=lambda kv: order.index(kv[1][0]))):
        ops = tuple(graph.task(n).op for n in names)
        groups.append(FusionGroup(gid, names, ops))
        for n in names:
            graph.task(n).fused_group = gid
    return groups


# Memoized lowerings: structural key -> LoweredProgram (LRU).
_LOWER_CACHE: OrderedDict[tuple, LoweredProgram] = OrderedDict()
_LOWER_LOCK = threading.Lock()
_COUNT_LOCK = threading.Lock()
# Memo hits and misses; per jitted program (keyed by its name,
# ``codo_<graph>``), the calls (``dispatches``), the runs of its Python
# body (``traces``): under jit, only when JAX traces it — a compile or a
# persistent-cache load — and on every call without jit; the conv tasks it
# runs as patch matmuls (``patch_convs``, set at lowering); and the
# candidate chains the gate declined (``declined``, set at lowering): a
# Counter keyed by (pattern, reason), the reason ``infeasible`` (the
# pattern's guard), ``cost`` (the cost model or tuning database), ``vmem``
# (the factory: a grid step's blocks exceed VMEM) or ``sharded``.
LOWER_CACHE_STATS = {"hits": 0, "misses": 0,
                     "dispatches": Counter(), "traces": Counter(),
                     "patch_convs": Counter(), "declined": {}}


def _lower_cache_size() -> int:
    return max(1, int(os.environ.get("CODO_LOWER_CACHE_SIZE", "64")))


def clear_lower_cache() -> None:
    with _LOWER_LOCK:
        _LOWER_CACHE.clear()
        LOWER_CACHE_STATS.update(hits=0, misses=0)
    with _COUNT_LOCK:
        LOWER_CACHE_STATS["dispatches"].clear()
        LOWER_CACHE_STATS["traces"].clear()
        LOWER_CACHE_STATS["patch_convs"].clear()
        LOWER_CACHE_STATS["declined"].clear()


def program_name(graph: DataflowGraph) -> str:
    """``codo_<graph name>`` as an identifier: the name of the graph's
    jitted program.  It enters JAX's persistent-cache key, so it depends
    on nothing but the graph's name."""
    return "codo_" + re.sub(r"\W", "_", graph.name)


def _build_steps(graph: DataflowGraph, groups: list[FusionGroup],
                 use_registered_kernels: bool
                 ) -> list[tuple[str, str, Callable[[dict], dict]]]:
    """The executable step list, ``(task, scope, fn)``: routed chains
    become one kernel step emitted at the chain's *last* task position
    (every external operand of every matched task is in scope by then);
    everything else runs task by task (XLA still fuses under jit).  The
    scope, ``g<gid>.<kernel>`` for a routed chain and ``g<gid>.xla`` for
    the rest, names the step's ops in the compiled program; a task traced
    in a ``frontend.region`` prefixes it with the region's name (per step:
    one fusion group can span many layers)."""
    step_at: dict[str, tuple[str, Callable[[dict], dict]]] = {}
    skip: set[str] = set()
    if use_registered_kernels:
        from .routing import registered_patterns
        pats = {p.name: p for p in registered_patterns()}
        for g in groups:
            built: list[RoutedKernel] = []
            for route in g.routes:
                pat = pats.get(route.kernel)
                tasks = [graph.task(n) for n in route.tasks]
                if pat is None:
                    step = None
                elif route.tile is not None:    # tuned blocking wins
                    step = pat.factory(graph, g, tasks, tile=route.tile)
                else:
                    step = pat.factory(graph, g, tasks)
                if step is None:        # factory declined at build time
                    continue
                built.append(route)
                step_at[route.tasks[-1]] = (route.kernel, step)
                skip.update(route.tasks[:-1])
            if len(built) != len(g.routes):
                for r in g.routes:
                    if r not in built:
                        r.decision = "declined"     # factory said no
                        g.rejected.append(r)
                g.routes = built
                g.kernel = ("pallas:" + "+".join(r.kernel for r in built)
                            if built else XLA_FUSED)
                g.decision = "routed" if built else "generic"
    else:
        for g in groups:
            g.routes, g.rejected = [], []
            g.kernel = XLA_FUSED
            g.decision = "generic"

    steps = []
    for t in graph.toposort():
        if t.name in skip:
            continue
        if t.name in step_at:
            kernel, fn = step_at[t.name]
        elif takes_patch_conv(graph, t):
            kernel, fn = "patchconv", _patch_conv_step(t.spec)
        else:
            kernel, fn = "xla", t.fn
        region = task_region(t)
        steps.append((t.name, (f"{region}." if region else "")
                      + f"g{t.fused_group}.{kernel}", fn))
    return steps


# Why a candidate chain stayed generic, by its route's decision.
_DECLINE_REASON = {"predicted-loss": "cost", "tuned-generic": "cost",
                   "declined": "vmem", "sharded": "sharded"}


def declined_chains(groups: list[FusionGroup],
                    infeasible: list) -> Counter:
    """(pattern, reason) -> candidate chains the gate declined."""
    out = Counter(infeasible)
    for g in groups:
        for r in g.rejected:
            out[(r.kernel, _DECLINE_REASON.get(r.decision, r.decision))] += 1
    return out


# An ungrouped convolution with few input channels leaves the 128 lanes of
# XLA's convolution mostly empty (C_in = 3: 0.31 TFLOP/s on a v5e, against
# ~6.4 at C_in >= 128).  Its patch matrix, C_in*kh*kw deep, fills them.
LANES = 128
# Most input channels a convolution may have to run as a patch matmul.  In
# ResNet-18's program on a v5e each convolution of C_in <= 64 gains when it
# alone is patched: the stem 740 µs a run, the two 64->64 ones 104 µs each,
# the 64->128 3x3/2 4 µs; those of C_in >= 128 lose 274 µs together.  There
# XLA's convolutions read C on the lanes, which 64 channels fill half (the
# 64->64 ones, reading a routed kernel's output, take 133 µs).  Jitted
# alone the C_in = 64 ones tie or lose, so a program that lays them out
# otherwise may gain nothing from them (docs/architecture.md §5).
PATCH_CONV_MAX_CIN = 64


def patch_conv_rule(groups: int, c_in: int, kh: int, kw: int) -> bool:
    """Whether an OIHW convolution of these shapes runs as a patch matmul:
    ungrouped, with few input channels, and a patch at least a lane deep."""
    return (groups == 1 and c_in <= PATCH_CONV_MAX_CIN
            and c_in * kh * kw >= LANES)


def takes_patch_conv(graph: DataflowGraph, t: Task) -> bool:
    """A ``conv2d`` task (declarative, no closure override) whose weight's
    shapes pass :func:`patch_conv_rule`."""
    spec = t.spec
    if spec is None or spec.kind != "conv2d" or t.fn_is_closure:
        return False
    _o, c_in, kh, kw = graph.buffers[spec.ins[1]].shape
    return patch_conv_rule(int(spec.attrs.get("groups", 1)), c_in, kh, kw)


def _phase_major(x, s: int):
    """``x`` (NCHW) with its rows and columns reordered by their phase
    modulo ``s``: row ``s*a + p`` moves to ``p*ceil(H/s) + a`` (zero rows
    pad H to a multiple of ``s``), and the same for columns.  Pure data
    movement, and no lane-strided access: a strided slice along the lanes
    costs a v5e ~110 µs for the stem's 3x112x112 tap."""
    n, c, h, w = x.shape
    hp, wp = -(-h // s) * s, -(-w // s) * s
    x = jnp.pad(x, ((0, 0), (0, 0), (0, hp - h), (0, wp - w)))
    x = x.reshape(n, c, hp // s, s, wp).transpose(0, 1, 3, 2, 4)
    x = x.reshape(n, c, hp, wp).transpose(0, 1, 3, 2)       # columns on rows
    x = x.reshape(n, c, wp // s, s, hp).transpose(0, 1, 3, 2, 4)
    return x.reshape(n, c, wp, hp).transpose(0, 1, 3, 2)


def patch_conv2d(x, w, stride: int):
    """``conv2d``'s VALID NCHW x OIHW convolution as one matmul.  The patch
    matrix holds, for tap ``(i, j)`` and output ``(oh, ow)``, the input at
    ``(s*oh + i, s*ow + j)``: after :func:`_phase_major` every tap is a
    contiguous window, cut as ``kw`` column windows and then ``kh`` row
    windows of those.  It is contracted over ``(c, kh, kw)`` with the
    weight at ``HIGHEST``; the output dimensions stay on the lanes.
    (``lax.conv_general_dilated_patches`` is itself a convolution with C
    input channels, and would leave the lanes as empty.)"""
    n, c, h, wd = x.shape
    o, _c, kh, kw = w.shape
    s = stride
    oh, ow = (h - kh) // s + 1, (wd - kw) // s + 1
    if s > 1:
        x = _phase_major(x, s)
    hq, wq = x.shape[2] // s, x.shape[3] // s
    cols = jnp.stack([jax.lax.slice_in_dim(x, (j % s) * wq + j // s,
                                           (j % s) * wq + j // s + ow, axis=3)
                      for j in range(kw)], axis=3)      # n c H kw ow
    taps = jnp.stack([jax.lax.slice_in_dim(cols, (i % s) * hq + i // s,
                                           (i % s) * hq + i // s + oh, axis=2)
                      for i in range(kh)], axis=2)      # n c kh oh kw ow
    y = jax.lax.dot_general(w, taps, (((1, 2, 3), (1, 2, 4)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST)
    return y.transpose(1, 0, 2, 3)                      # o n oh ow -> NCHW


def _patch_conv_step(spec) -> Callable[[dict], dict]:
    x, w = spec.ins
    out, stride = spec.outs[0], int(spec.attrs.get("stride", 1))
    return lambda env: {out: patch_conv2d(env[x], env[w], stride)}


def _drop_sharded_routes(groups: list[FusionGroup], sharding) -> None:
    """Un-route any chain a collective lands inside.  A routed kernel step
    runs at the chain's *last* task with the interiors skipped, so the
    only anchor the sharded executor can honor is "after the last task"
    (the psum on a row-parallel matmul's output).  A gather before any
    chain task, or a reduction after an interior, would silently never
    run — fall back to the generic per-task path for that chain."""
    from repro.distributed import collectives as _coll
    before, after = _coll.attach(sharding.steps)
    for g in groups:
        kept = []
        for r in g.routes:
            bad = any(t in before for t in r.tasks) or \
                any(t in after for t in r.tasks[:-1])
            if bad:
                r.decision = "sharded"      # collective inside the chain
                g.rejected.append(r)
            else:
                kept.append(r)
        if len(kept) != len(g.routes):
            g.routes = kept
            g.kernel = ("pallas:" + "+".join(r.kernel for r in kept)
                        if kept else XLA_FUSED)
            g.decision = "routed" if kept else "generic"


def _sharded_program(graph: DataflowGraph, steps, outputs: list[str],
                     sharding) -> Callable[[dict], dict]:
    """Wrap the step list in ``shard_map`` over the plan's mesh.

    Inside the body every env entry is the *local shard* its
    :class:`ShardSpec` dictates; the plan's collective schedule rewrites
    scope values before the consumer that needs the full buffer
    (all_gather) and after the producer that left partial sums
    (psum / reduce_scatter+all_gather / ppermute ring)."""
    from repro.distributed import collectives as _coll
    from repro.launch.mesh import mesh_from_spec

    before, after = _coll.attach(sharding.steps)
    emitted = {name for name, _scope, _f in steps}
    missing = [s.task for s in sharding.steps if s.task not in emitted]
    if missing:        # _drop_sharded_routes guarantees this never fires
        raise GraphError(
            f"collective anchored on skipped task(s) {missing}")
    fns = {id(s): _coll.make_collective(s, sharding.mesh)
           for s in sharding.steps}
    in_specs, out_specs = _coll.env_partition_specs(graph, sharding)
    mesh = mesh_from_spec(sharding.mesh)

    def body(env: dict) -> dict:
        scope = dict(env)
        for name, label, f in steps:
            for s in before.get(name, ()):
                scope[s.buffer] = fns[id(s)](scope[s.buffer])
            with jax.named_scope(label):
                scope.update(f(scope))
            for s in after.get(name, ()):
                scope[s.buffer] = fns[id(s)](scope[s.buffer])
        return {k: scope[k] for k in outputs}

    mapped = jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                           out_specs=out_specs, check_vma=False)

    def program(env: dict) -> dict:
        extra = set(env) - set(in_specs)
        if extra:
            raise GraphError(f"sharded program got unexpected env keys "
                             f"{sorted(extra)}")
        return mapped(dict(env))

    return program


def lower(compiled: CompiledDataflow, jit: bool = True,
          use_registered_kernels: bool = True, *,
          memo: bool = True, sharding=None) -> LoweredProgram:
    # The compiler — not the user — wires the Pallas kernels in.
    ensure_kernel_patterns()
    graph = compiled.graph
    stripped = [t.name for t in graph.tasks if t.fn is None]
    if stripped:
        raise GraphError(
            f"cannot lower {graph.name}: {len(stripped)} tasks have no numeric "
            f"semantics (e.g. {stripped[0]!r}). These tasks were built from "
            "raw closures (not picklable), so their disk compile-cache entry "
            "is structural-only; build graphs with declarative OpSpecs "
            "(repro.core.ops) for executable cache entries, or recompile "
            "with an in-memory cache / cache=None before lowering.")
    # Key covers fusion decisions (via the structural hash), the flags, and
    # every routing-relevant switch (routing_state_key: the disable/force
    # escape hatches, the registry epoch, the priced backend, the
    # calibration digest, and the tuning-DB digest) plus the op-impl
    # registry epoch — flipping any of them must never serve a stale
    # program.
    key = (graph.structural_hash(), bool(jit), bool(use_registered_kernels),
           pallas_interpret_forced(), *routing_state_key(), _ops_epoch(),
           sharding.digest() if sharding is not None else "")
    if memo:
        with _LOWER_LOCK:
            hit = _LOWER_CACHE.get(key)
            if hit is not None:
                _LOWER_CACHE.move_to_end(key)
                LOWER_CACHE_STATS["hits"] += 1
        if hit is not None:
            # Mirror the cached fusion decisions onto the caller's graph so
            # post-lowering introspection (fused_group ids) behaves as if
            # the lowering had run, then share the built program.
            for g in hit.groups:
                for n in g.tasks:
                    graph.task(n).fused_group = g.gid
            _record_routing(compiled, hit.groups)
            # The memo key leaves the graph's name out: a structurally
            # identical graph shares the program and the first one's name.
            return LoweredProgram(graph, hit.groups, hit.fn,
                                  list(hit.materialized), hit.name,
                                  hit.patch_convs)
    impl = compiled.buffer_plan.impl if compiled.buffer_plan else {}
    groups = fusion_groups(graph, impl)
    infeasible: list = []
    if use_registered_kernels:
        route_groups(graph, groups, impl,
                     hw=device_hw(compiled.options.hw), declined=infeasible)
    if sharding is not None:
        _drop_sharded_routes(groups, sharding)
    steps = _build_steps(graph, groups, use_registered_kernels)

    outputs = [b.name for b in graph.outputs()]
    # Interior buffers of routed chains never leave the kernel — even the
    # ping-pong-planned ones the generic path would bounce through HBM.
    swallowed = {graph.task(n).writes[0].buffer
                 for g in groups for r in g.routes for n in r.tasks[:-1]}
    materialized = [b.name for b in graph.intermediates()
                    if impl.get(b.name) == "pingpong"
                    and b.name not in swallowed]

    if sharding is None:
        def body(env: dict) -> dict:
            scope = dict(env)
            for _name, label, f in steps:
                with jax.named_scope(label):
                    scope.update(f(scope))
            return {k: scope[k] for k in outputs}
    else:
        body = _sharded_program(graph, steps, outputs, sharding)

    name = program_name(graph)

    def program(env: dict) -> dict:
        with _COUNT_LOCK:
            LOWER_CACHE_STATS["traces"][name] += 1
        return body(env)

    program.__name__ = name
    fn = jax.jit(program) if jit else program
    patch_convs = sum(scope.endswith(".patchconv") for _n, scope, _f in steps)
    with _COUNT_LOCK:
        LOWER_CACHE_STATS["patch_convs"][name] = patch_convs
        LOWER_CACHE_STATS["declined"][name] = declined_chains(groups,
                                                              infeasible)
    out = LoweredProgram(graph, groups, fn, materialized, name, patch_convs)
    _record_routing(compiled, groups)
    if memo:
        with _LOWER_LOCK:
            LOWER_CACHE_STATS["misses"] += 1
            _LOWER_CACHE[key] = out
            _LOWER_CACHE.move_to_end(key)
            while len(_LOWER_CACHE) > _lower_cache_size():
                _LOWER_CACHE.popitem(last=False)
    return out


def _record_routing(compiled: CompiledDataflow,
                    groups: list[FusionGroup]) -> None:
    """Surface the routing decision on the design's diagnostics so it
    travels with reports, ``--profile`` tables, and exported artifacts.
    Every entry records the cost gate's verdict and the predicted cycles
    both ways (ISSUE 6), not just the winning kernel name."""
    if compiled.diagnostics is not None:
        compiled.diagnostics.group_kernels = {
            str(g.gid): {
                "kernel": g.kernel,
                "decision": g.decision,
                "predicted_routed_cycles": round(
                    g.predicted_routed_cycles, 1),
                "predicted_generic_cycles": round(
                    g.predicted_generic_cycles, 1),
                "routes": [r.to_dict() for r in g.routes],
                "rejected": [r.to_dict() for r in g.rejected],
            } for g in groups}


def lower_artifact(source, *, jit: bool = True,
                   use_registered_kernels: bool = True, memo: bool = True,
                   check_integrity: bool = True) -> LoweredProgram:
    """One-step path from an exported JSON artifact (a file path or parsed
    document — see docs/artifact_format.md) to an executable program:
    ``import_artifact`` + :func:`lower`.  The artifact must have been
    exported from a spec-carrying design; op kinds resolve against this
    process's registry."""
    from .artifact import import_artifact  # lazy: artifact stays jax-free
    return lower(import_artifact(source, check_integrity=check_integrity),
                 jit=jit, use_registered_kernels=use_registered_kernels,
                 memo=memo)


def oracle_outputs(source_graph: DataflowGraph, env: dict) -> dict:
    """Run the *un-optimized* program — the golden reference the paper's
    auto-generated testbench compares against (§VII-C)."""
    return source_graph.execute(env)


def verify_lowering(source_graph: DataflowGraph, compiled: CompiledDataflow,
                    env: dict, rtol: float = 1e-5, atol: float = 1e-5,
                    sharding=None) -> None:
    """Lowered outputs must match the un-optimized oracle.  With a
    ``sharding`` plan the multi-device lowering is checked instead; the
    default tolerances absorb the one reassociation a psum introduces
    (a tree-reduce over device partials vs the serial contraction —
    everything collective-free stays bit-identical)."""
    got = lower(compiled, jit=False, sharding=sharding)(env)
    want = oracle_outputs(source_graph, env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"output {k} diverged after lowering")


def verify_sharding(compiled: CompiledDataflow, sharding, env: dict,
                    rtol: float = 1e-4, atol: float = 5e-5) -> None:
    """Assert the sharded lowering matches the single-device lowering on
    ``env`` within documented fp tolerance.  Two reassociations are
    expected and bounded, nothing else may differ: a psum tree-reduces
    device partials where the serial contraction sums in order, and even
    gather-only plans run matmuls at *local* shapes, where XLA may pick a
    different (equally valid) contraction order.  Defaults hold the GPT-2
    block to ~1e-5 on CPU; genuine sharding bugs (wrong shard, missing
    collective) produce O(1) errors, orders of magnitude past the gate."""
    single = lower(compiled, jit=False)(env)
    shard = lower(compiled, jit=False, sharding=sharding)(env)
    for k in single:
        np.testing.assert_allclose(
            np.asarray(shard[k]), np.asarray(single[k]), rtol=rtol,
            atol=atol,
            err_msg=f"output {k}: sharded lowering diverged beyond the "
                    f"fp-reassociation tolerance")


def verify_routing(compiled: CompiledDataflow, env: dict,
                   rtol: float = 1e-5, atol: float = 1e-5) -> LoweredProgram:
    """Assert the pattern-routed lowering matches the un-routed generic
    lowering on ``env`` — the same executable-comparison check
    :func:`verify_lowering` performs against the oracle, aimed at the
    routing layer specifically.  Returns the routed program."""
    generic = lower(compiled, jit=False, use_registered_kernels=False)
    routed = lower(compiled, jit=False, use_registered_kernels=True)
    got, want = routed(env), generic(env)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=rtol, atol=atol,
            err_msg=f"output {k}: pattern-routed kernel diverged from the "
                    f"xla-fused lowering")
    return routed
