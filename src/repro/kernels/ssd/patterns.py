"""CODO kernel-pattern registration: the SSD inter-chunk state scan.

``ssd.scan`` claims the single ``scan`` task a traced
``F.ssd_scan(states, decay)`` emits (carried-in chunk states over
``(nc, BH, P, N)`` end-states and ``(nc, BH, 1, 1)`` decays) and
replaces its sequential generic lowering with the chunk-scan Pallas
kernel — a one-task chain, hence ``allow_single=True``.
"""

from __future__ import annotations

import functools

from ...core.routing import KernelPattern, register_kernel_pattern
from ..common import all_f32, kernel_mode, vmem_ok, vmem_tile


def _feasible(graph, tasks) -> bool:
    (t,) = tasks
    if t.spec is None or t.spec.kind != "ssd_scan":
        return False
    st_buf, dec_buf = t.spec.ins
    out_buf = t.spec.outs[0]
    st_shape = graph.buffers[st_buf].shape
    dec_shape = graph.buffers[dec_buf].shape
    if len(st_shape) != 4 or len(dec_shape) != 4:
        return False
    if dec_shape[:2] != st_shape[:2] or dec_shape[2:] != (1, 1):
        return False
    return all_f32(graph, st_buf, dec_buf, out_buf)


def step_vmem_ok(states_shape) -> bool:
    """What one grid step of the kernel holds in VMEM: one chunk's state
    block and decay block in, the carried-in state block out (each
    double-buffered), and the running state."""
    _nc, bh, p, n = states_shape
    state = vmem_tile((bh, p, n))
    return vmem_ok((2, *state), (2, *vmem_tile((bh, 1, 1))), (2, *state),
                   state)


def factory(graph, group, tasks):
    import jax

    (t,) = tasks
    st_buf, dec_buf = t.spec.ins
    out_buf = t.spec.outs[0]

    mode = kernel_mode()
    if mode == "pallas" and not step_vmem_ok(graph.buffers[st_buf].shape):
        return None                     # a grid step's blocks exceed VMEM

    if mode == "reference":
        from .ref import ssd_chunk_scan_ref
        fn = jax.jit(ssd_chunk_scan_ref)
    else:
        from .ssd import ssd_chunk_scan
        fn = jax.jit(functools.partial(ssd_chunk_scan,
                                       interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[st_buf], env[dec_buf])}

    return run


_REGISTERED = False


def register() -> None:
    """Register the ssd kernel pattern (idempotent)."""
    global _REGISTERED
    if _REGISTERED:
        return
    _REGISTERED = True
    register_kernel_pattern(KernelPattern(
        name="ssd.scan", pattern=("scan",),
        factory=factory, feasible=_feasible,
        allow_single=True,
        description="Mamba-2 SSD inter-chunk state scan "
                    "(replaces the sequential generic scan)"))
