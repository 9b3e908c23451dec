"""Jit'd wrappers + CODO kernel-pattern registration for the streamfuse
fused kernels.

``register()`` hooks four :class:`~repro.core.routing.KernelPattern`\\ s
into the compiler's routing layer:

=======================  ===========================  =====================
pattern name             op pattern                   kernel
=======================  ===========================  =====================
``streamfuse.conv``      ``pad → conv → ewise``       ``fused_pad_conv_relu``
``streamfuse.mmchain``   ``matmul → *ewise → matmul`` ``fused_matmul_chain``
``streamfuse.softmaxmm`` ``softmax → matmul``         ``fused_softmax_matmul``
``streamfuse.mmgrad``    ``matmul → *ewise`` (grad)   ``fused_matmul_grad``
=======================  ===========================  =====================

Feasibility guards are pure graph analysis (spec kinds, strides, ranks,
dtypes) so the routing decision itself stays jax-free; backend selection
happens in the factories:

* on TPU the Pallas kernel runs compiled (declining chains whose resident
  weights would blow the VMEM budget);
* ``CODO_PALLAS_INTERPRET=1`` forces the Pallas kernel in interpret mode
  (how CI exercises the real kernel path on CPU);
* otherwise (CPU/GPU hosts) the kernel's fused jnp reference runs as one
  jit'd function — the same fusion decision, carried by XLA:CPU.
"""

from __future__ import annotations

import functools

from ...core.ops import op_impl
from ...core.routing import KernelPattern, register_kernel_pattern
from ..common import all_f32, kernel_mode, vmem_ok
from .ref import (matmul_chain_ref, matmul_grad_ref, pad_conv_relu_ref,
                  softmax_matmul_ref)

# Elementwise spec kinds a kernel can replay on a VMEM block: exactly one
# operand (the chain value), attrs-only parameters.
EW_KINDS = frozenset({"relu", "gelu", "scale", "affine", "divc", "rdivc",
                      "identity"})

# Gradient-epilogue kinds (backward chains): chain value first operand,
# residual operands stream alongside it with the same row-blocking.
GRAD_EW_KINDS = frozenset({"relu_grad", "gelu_grad", "softmax_grad"})


# --------------------------------------------------------------------------
# pad -> conv -> relu (the Fig. 2 motivating chain)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _ref_conv_jit():
    import jax
    return jax.jit(pad_conv_relu_ref)


def pad_conv_relu(x, w, *, use_kernel: bool = True):
    """relu(conv2d(pad(x), w)), stride 1, SAME; backend-dispatched."""
    if not use_kernel:
        return _ref_conv_jit()(x, w)
    mode = kernel_mode()
    if mode == "reference":
        return _ref_conv_jit()(x, w)
    from .streamfuse import fused_pad_conv_relu
    return fused_pad_conv_relu(x, w, interpret=(mode == "interpret"))


def _conv_feasible(graph, tasks) -> bool:
    pad_t, conv_t, relu_t = tasks
    if any(t.spec is None for t in tasks):
        return False
    if (pad_t.spec.kind, conv_t.spec.kind, relu_t.spec.kind) != (
            "pad2d", "conv2d", "relu"):
        return False
    if int(conv_t.spec.attrs.get("stride", 1)) != 1:
        return False
    if int(conv_t.spec.attrs.get("groups", 1)) != 1:
        return False
    if conv_t.spec.ins[0] != pad_t.spec.outs[0]:
        return False
    x_buf, w_buf = pad_t.spec.ins[0], conv_t.spec.ins[1]
    x_shape = graph.buffers[x_buf].shape
    w_shape = graph.buffers[w_buf].shape
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    k = w_shape[-1]
    if w_shape[-2] != k or k % 2 != 1:
        return False
    if int(pad_t.spec.attrs.get("pad", -1)) != k // 2:     # SAME only
        return False
    return all_f32(graph, x_buf, w_buf, relu_t.spec.outs[0])


def _conv_factory(graph, group, tasks):
    import jax

    pad_t, conv_t, relu_t = tasks
    x_buf, w_buf = pad_t.spec.ins[0], conv_t.spec.ins[1]
    out_buf = relu_t.spec.outs[0]

    mode = kernel_mode()           # resolved once; the lowering memo key
    if mode == "reference":        # covers the switches that change it
        fn = _ref_conv_jit()
    else:
        from .streamfuse import fused_pad_conv_relu
        fn = jax.jit(functools.partial(fused_pad_conv_relu,
                                       interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[x_buf], env[w_buf])}

    return run


# --------------------------------------------------------------------------
# matmul -> *ewise -> matmul
# --------------------------------------------------------------------------


def _mm_chain_feasible(graph, tasks) -> bool:
    first, last = tasks[0], tasks[-1]
    if any(t.spec is None for t in tasks):
        return False
    if first.spec.kind != "matmul" or last.spec.kind != "matmul":
        return False
    prev_out = first.spec.outs[0]
    for t in tasks[1:-1]:
        if t.spec.kind not in EW_KINDS or t.spec.ins != (prev_out,):
            return False
        prev_out = t.spec.outs[0]
    if last.spec.ins[0] != prev_out:    # chain value must stream in as LHS
        return False
    bufs = (*first.spec.ins, last.spec.ins[1], last.spec.outs[0])
    if any(len(graph.buffers[b].shape) != 2 for b in bufs[:3]):
        return False
    return all_f32(graph, *bufs)


def _ew_applier(ew_tasks):
    impls = [(op_impl(t.spec.kind), t.spec) for t in ew_tasks]

    def ew(h):
        for impl, spec in impls:
            h = impl(spec, {spec.ins[0]: h})[spec.outs[0]]
        return h

    return ew


def _mm_chain_factory(graph, group, tasks):
    import jax
    from .chain import fused_matmul_chain

    first, last = tasks[0], tasks[-1]
    a_buf, w1_buf = first.spec.ins
    w2_buf = last.spec.ins[1]
    out_buf = last.spec.outs[0]
    ew = _ew_applier(tasks[1:-1])

    mode = kernel_mode()
    if mode == "pallas" and not vmem_ok(graph.buffers[w1_buf].shape,
                                        graph.buffers[w2_buf].shape):
        return None                     # resident weights exceed VMEM
    if mode == "reference":
        fn = jax.jit(lambda a, w1, w2: matmul_chain_ref(a, w1, w2, ew))
    else:
        fn = jax.jit(functools.partial(fused_matmul_chain, ew=ew,
                                       block_m=128,
                                       interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[a_buf], env[w1_buf], env[w2_buf])}

    return run


# --------------------------------------------------------------------------
# matmul -> *ewise gradient epilogue (backward-pass chains)
# --------------------------------------------------------------------------


def _mm_grad_feasible(graph, tasks) -> bool:
    """Backward chains only: a cotangent matmul whose elementwise tail
    contains at least one gradient kind (so forward ``matmul → ewise``
    prefixes are never claimed and the longer ``mmchain`` match still
    supersedes this one over shared tasks)."""
    mm, tail = tasks[0], tasks[1:]
    if any(t.spec is None for t in tasks) or not tail:
        return False
    if mm.spec.kind != "matmul" or len(mm.spec.ins) != 2:
        return False
    a_buf, w_buf = mm.spec.ins
    mn = graph.buffers[mm.spec.outs[0]].shape
    shapes = (graph.buffers[a_buf].shape, graph.buffers[w_buf].shape, mn)
    if any(len(s) != 2 for s in shapes):
        return False
    prev, has_grad = mm.spec.outs[0], False
    for t in tail:
        kind = t.spec.kind
        if kind in GRAD_EW_KINDS:
            has_grad = True
        elif kind not in EW_KINDS:
            return False
        if not t.spec.ins or t.spec.ins[0] != prev:
            return False
        for b in t.spec.ins[1:]:        # residual operands ride the stream
            if graph.buffers[b].shape != mn or not all_f32(graph, b):
                return False
        if kind == "softmax_grad" and int(
                t.spec.attrs.get("axis", -1)) not in (-1, 1):
            return False                # row blocks span full rows only
        prev = t.spec.outs[0]
    if not has_grad:
        return False
    return all_f32(graph, a_buf, w_buf, tail[-1].spec.outs[0])


def _grad_ew_applier(tail_tasks):
    """Replay the gradient epilogue's registered impls on a VMEM block.
    Returns ``(ew, extra_bufs)``: ``ew(h, *extras)`` threads the chain
    value through each stage's first operand with the residual operands
    bound positionally from ``extra_bufs`` order."""
    impls = [(op_impl(t.spec.kind), t.spec) for t in tail_tasks]
    extra_bufs = [b for t in tail_tasks for b in t.spec.ins[1:]]

    def ew(h, *extras):
        env = dict(zip(extra_bufs, extras))
        for impl, spec in impls:
            env[spec.ins[0]] = h
            h = impl(spec, env)[spec.outs[0]]
        return h

    return ew, extra_bufs


def _mm_grad_factory(graph, group, tasks):
    import jax
    from .chain import fused_matmul_grad

    mm, tail = tasks[0], tasks[1:]
    a_buf, w_buf = mm.spec.ins
    out_buf = tail[-1].spec.outs[0]
    ew, extra_bufs = _grad_ew_applier(tail)

    mode = kernel_mode()
    if mode == "pallas" and not vmem_ok(graph.buffers[w_buf].shape):
        return None                     # resident operand exceeds VMEM
    if mode == "reference":
        fn = jax.jit(lambda a, w, *ex: matmul_grad_ref(a, w, ex, ew))
    else:
        fn = jax.jit(functools.partial(fused_matmul_grad, ew=ew,
                                       block_m=128,
                                       interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[a_buf], env[w_buf],
                            *(env[b] for b in extra_bufs))}

    return run


# --------------------------------------------------------------------------
# softmax -> matmul (attention tail)
# --------------------------------------------------------------------------


def _softmax_mm_feasible(graph, tasks) -> bool:
    sm, mm = tasks
    if sm.spec is None or mm.spec is None:
        return False
    if sm.spec.kind != "softmax" or mm.spec.kind != "matmul":
        return False
    s_shape = graph.buffers[sm.spec.ins[0]].shape
    if len(s_shape) != 2 or int(sm.spec.attrs.get("axis", -1)) not in (
            -1, len(s_shape) - 1):
        return False
    if mm.spec.ins[0] != sm.spec.outs[0]:   # probabilities stream in as LHS
        return False
    v_buf = mm.spec.ins[1]
    if len(graph.buffers[v_buf].shape) != 2:
        return False
    return all_f32(graph, sm.spec.ins[0], v_buf, mm.spec.outs[0])


def _softmax_mm_factory(graph, group, tasks):
    import jax
    from .chain import fused_softmax_matmul

    sm, mm = tasks
    s_buf, v_buf, out_buf = sm.spec.ins[0], mm.spec.ins[1], mm.spec.outs[0]

    mode = kernel_mode()
    if mode == "pallas" and not vmem_ok(graph.buffers[v_buf].shape):
        return None
    if mode == "reference":
        fn = jax.jit(softmax_matmul_ref)
    else:
        fn = jax.jit(functools.partial(
            fused_softmax_matmul, block_m=128, block_k=128,
            interpret=(mode == "interpret")))

    def run(env):
        return {out_buf: fn(env[s_buf], env[v_buf])}

    return run


# --------------------------------------------------------------------------
# Registration
# --------------------------------------------------------------------------

_REGISTERED = False


def register() -> None:
    """Register the streamfuse kernel patterns with the routing layer
    (idempotent — re-imports and repeated ``register_all()`` calls do not
    churn the registry epoch)."""
    global _REGISTERED
    if _REGISTERED:
        return
    _REGISTERED = True
    register_kernel_pattern(KernelPattern(
        name="streamfuse.conv", pattern=("pad", "conv", "ewise"),
        factory=_conv_factory, feasible=_conv_feasible,
        description="fused pad->conv3x3->relu streaming kernel (Fig. 2)"))
    register_kernel_pattern(KernelPattern(
        name="streamfuse.mmchain", pattern=("matmul", "*ewise", "matmul"),
        factory=_mm_chain_factory, feasible=_mm_chain_feasible,
        description="ew(a@w1)@w2 with the activation row-block in VMEM"))
    register_kernel_pattern(KernelPattern(
        name="streamfuse.softmaxmm", pattern=("softmax", "matmul"),
        factory=_softmax_mm_factory, feasible=_softmax_mm_feasible,
        description="online-softmax(s)@v streaming attention tail"))
    register_kernel_pattern(KernelPattern(
        name="streamfuse.mmgrad", pattern=("matmul", "*ewise"),
        factory=_mm_grad_factory, feasible=_mm_grad_feasible,
        description="cotangent matmul with fused gradient epilogue "
                    "(backward chains)"))
