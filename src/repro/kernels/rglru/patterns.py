"""CODO kernel-pattern registration: the RG-LRU linear recurrence.

``rglru.scan`` claims the single ``scan`` task a traced
``F.rglru_scan(a, b)`` emits (``h_t = a_t·h_{t-1} + b_t`` over axis 1 of
``(B, S, D)`` operands) and replaces its sequential generic lowering
with the chunked-scan Pallas kernel — a one-task chain, hence
``allow_single=True``.
"""

from __future__ import annotations

import functools

from ...core.routing import KernelPattern, register_kernel_pattern
from ..common import all_f32, kernel_mode, pow2_block, vmem_ok


def _feasible(graph, tasks) -> bool:
    (t,) = tasks
    if t.spec is None or t.spec.kind != "rglru_scan":
        return False
    a_buf, b_buf = t.spec.ins
    out_buf = t.spec.outs[0]
    a_shape = graph.buffers[a_buf].shape
    if len(a_shape) != 3 or graph.buffers[b_buf].shape != a_shape:
        return False
    return all_f32(graph, a_buf, b_buf, out_buf)


def factory(graph, group, tasks):
    import jax

    (t,) = tasks
    a_buf, b_buf = t.spec.ins
    out_buf = t.spec.outs[0]
    s = graph.buffers[a_buf].shape[1]

    mode = kernel_mode()
    if mode == "pallas" and not vmem_ok(graph.buffers[a_buf].shape,
                                        graph.buffers[b_buf].shape):
        return None

    if mode == "reference":
        from .ref import rglru_ref
        fn = jax.jit(rglru_ref)
    else:
        from .rglru import rglru_scan
        fn = jax.jit(functools.partial(rglru_scan, chunk=pow2_block(s),
                                       interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[a_buf], env[b_buf])}

    return run


_REGISTERED = False


def register() -> None:
    """Register the rglru kernel pattern (idempotent)."""
    global _REGISTERED
    if _REGISTERED:
        return
    _REGISTERED = True
    register_kernel_pattern(KernelPattern(
        name="rglru.scan", pattern=("scan",),
        factory=factory, feasible=_feasible,
        allow_single=True,
        description="chunked RG-LRU linear recurrence h=a·h+b "
                    "(replaces the sequential generic scan)"))
