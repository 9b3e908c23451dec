"""CODO kernel-pattern registration: the full attention chain.

``flashattn.mha`` claims ``matmul -> *ewise -> softmax -> matmul`` — the
whole ``softmax(mask(c * q @ kᵀ)) @ v`` chain a traced attention block
emits (2-D single-head or 3-D heads-leading operands).  It anchors at
the *first* attention matmul, which precedes the softmax in topo order,
so a feasible match supersedes the narrower ``streamfuse.softmaxmm``
tail: the online-softmax stream starts at the score matmul and the S×S
score matrix never materializes in HBM.

Interior ``scale`` tasks fold into the kernel's internal 1/√hd by
pre-scaling q with ``c·√hd``; one ``causal_mask`` task runs the kernel
with ``causal=True``.  When both ``kᵀ`` and ``v`` come from
``repeat_heads`` tasks of one group size (grouped-query attention), the
kernel reads their sources, the K/V heads themselves, and its index map
sends query head ``h`` to K/V head ``h // group``: the repeated copies
are never read.
"""

from __future__ import annotations

import functools
import math

from ...core.routing import KernelPattern, register_kernel_pattern
from ..common import all_f32, kernel_mode, pow2_block, vmem_ok, vmem_tile


def _chain_parts(tasks):
    """(mm1, interior ewise list, softmax, mm2) or None if kinds drift."""
    if any(t.spec is None for t in tasks) or len(tasks) < 3:
        return None
    mm1, sm, mm2 = tasks[0], tasks[-2], tasks[-1]
    ews = tasks[1:-2]
    if (mm1.spec.kind, sm.spec.kind, mm2.spec.kind) != (
            "matmul", "softmax", "matmul"):
        return None
    return mm1, ews, sm, mm2


def _interiors_ok(mm1, ews, sm) -> bool:
    """Interiors are rescales of the chain value (the 1/√hd) and at most
    one causal mask (−∞ strictly above the diagonal); a scale after the
    mask must keep −∞ at −∞."""
    prev, masked = mm1.spec.outs[0], False
    for t in ews:
        if t.spec.ins != (prev,):
            return False
        if t.spec.kind == "causal_mask" and not t.spec.attrs and not masked:
            masked = True
        elif t.spec.kind != "scale" or (masked and
                                        float(t.spec.attrs["s"]) <= 0):
            return False
        prev = t.spec.outs[0]
    return sm.spec.ins[0] == prev


def _is_causal(ews) -> bool:
    return any(t.spec.kind == "causal_mask" for t in ews)


def kv_sources(graph, tasks):
    """``(kᵀ, v, group)``: the buffers the kernel reads for the chain's
    ``kᵀ`` and ``v`` operands.  Where both are ``repeat_heads`` outputs of
    one group size, their sources and that size; else the operands and 1."""
    kt_buf, v_buf = tasks[0].spec.ins[1], tasks[-1].spec.ins[1]
    srcs = []
    for buf in (kt_buf, v_buf):
        prods = graph.producers(buf)
        spec = prods[0].spec if len(prods) == 1 else None
        if spec is None or spec.kind != "repeat_heads":
            return kt_buf, v_buf, 1
        srcs.append((spec.ins[0], int(spec.attrs["groups"])))
    (kt_src, gk), (v_src, gv) = srcs
    if gk != gv:
        return kt_buf, v_buf, 1
    return kt_src, v_src, gk


def _feasible(graph, tasks) -> bool:
    parts = _chain_parts(tasks)
    if parts is None:
        return False
    mm1, ews, sm, mm2 = parts
    if not _interiors_ok(mm1, ews, sm) or mm2.spec.ins[0] != sm.spec.outs[0]:
        return False
    q_buf, kt_buf = mm1.spec.ins
    v_buf, out_buf = mm2.spec.ins[1], mm2.spec.outs[0]
    q_shape = graph.buffers[q_buf].shape
    kt_shape = graph.buffers[kt_buf].shape
    v_shape = graph.buffers[v_buf].shape
    if len(q_shape) not in (2, 3) or len(kt_shape) != len(q_shape) \
            or len(v_shape) != len(q_shape):
        return False
    hd = q_shape[-1]
    if kt_shape[-2] != hd or v_shape[-1] != hd:       # kt is (.., hd, Sk)
        return False
    if v_shape[-2] != kt_shape[-1]:                   # Sk agreement
        return False
    if len(q_shape) == 3 and not (q_shape[0] == kt_shape[0] == v_shape[0]):
        return False
    axis = int(sm.spec.attrs.get("axis", -1))
    if axis not in (-1, len(q_shape) - 1):
        return False
    return all_f32(graph, q_buf, kt_buf, v_buf, out_buf)


def step_vmem_ok(hd: int, bq: int, bk: int) -> bool:
    """What one grid step holds in VMEM: the q, k, v and output blocks,
    each double-buffered, and the (m, l, acc) scratch."""
    blocks = [(2, *vmem_tile((bq, hd))), (2, *vmem_tile((bk, hd))),
              (2, *vmem_tile((bk, hd))), (2, *vmem_tile((bq, hd))),
              vmem_tile((bq, 1)), vmem_tile((bq, 1)), vmem_tile((bq, hd))]
    return vmem_ok(*blocks)


def _scale_of(ews) -> float:
    c = 1.0
    for t in ews:
        c *= float(t.spec.attrs.get("s", 1.0))
    return c


def factory(graph, group, tasks):
    import jax
    import jax.numpy as jnp

    parts = _chain_parts(tasks)
    mm1, ews, sm, mm2 = parts
    q_buf, kt_buf = mm1.spec.ins
    v_buf, out_buf = mm2.spec.ins[1], mm2.spec.outs[0]
    q_shape = graph.buffers[q_buf].shape
    sq, hd = q_shape[-2], q_shape[-1]
    sk = graph.buffers[kt_buf].shape[-1]
    c = _scale_of(ews)
    causal = _is_causal(ews)
    bq, bk = pow2_block(sq), pow2_block(sk)

    mode = kernel_mode()
    if mode == "pallas" and not step_vmem_ok(hd, bq, bk):
        return None                     # a grid step's blocks exceed VMEM

    if mode == "reference":
        # Exactly the chain's computation, fused under one jit.
        def mha_ref(q, kt, v, _c=c):
            s = _c * jnp.matmul(q, kt, precision=jax.lax.Precision.HIGHEST)
            if causal:
                keep = (jnp.arange(s.shape[-2])[:, None]
                        >= jnp.arange(s.shape[-1])[None, :])
                s = jnp.where(keep, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.matmul(p, v, precision=jax.lax.Precision.HIGHEST)
        fn = jax.jit(mha_ref)
        k_in, v_in = kt_buf, v_buf
    else:
        from .flashattn import flash_attention
        kernel = functools.partial(flash_attention, causal=causal, window=0,
                                   block_q=bq, block_k=bk,
                                   interpret=(mode == "interpret"))
        k_in, v_in, _group = kv_sources(graph, tasks)
        # The kernel divides scores by √hd internally; fold the chain's
        # scale c in by pre-scaling q with c·√hd.
        pre = c * math.sqrt(hd)

        def mha_kernel(q, kt, v, _pre=pre, _kernel=kernel):
            batched = q.ndim == 3
            if not batched:
                q, kt, v = q[None], kt[None], v[None]
            qq = (q * _pre)[None]                         # (1, Hq, Sq, hd)
            kk = jnp.swapaxes(kt, -1, -2)[None]           # (1, Hkv, Sk, hd)
            o = _kernel(qq, kk, v[None])[0]
            return o if batched else o[0]
        fn = jax.jit(mha_kernel)

    def run(env):
        return {out_buf: fn(env[q_buf], env[k_in], env[v_in])}

    return run


_REGISTERED = False


def register() -> None:
    """Register the flashattn kernel pattern (idempotent)."""
    global _REGISTERED
    if _REGISTERED:
        return
    _REGISTERED = True
    register_kernel_pattern(KernelPattern(
        name="flashattn.mha",
        pattern=("matmul", "*ewise", "softmax", "matmul"),
        factory=factory, feasible=_feasible,
        description="full softmax(c·q@kᵀ)@v chain via online-softmax "
                    "streaming (supersedes the softmaxmm tail)"))
