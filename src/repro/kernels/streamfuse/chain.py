"""Streaming Pallas kernels for the dense fusion-group chains the router
maps (matmul→\\*ewise→matmul chains and softmax·matmul attention tails).

Both kernels follow the same CODO playbook as the conv chain in
``streamfuse.py``: the FIFO between the fused tasks is a VMEM value that
never round-trips through HBM, and reductions are rewritten to emit each
output element exactly once (Fig. 5).

``fused_matmul_chain`` — ``ew(a @ w1) @ w2`` with the intermediate
activation row-block resident in VMEM.  Grid: ``(M/bm,)`` — one
activation row-block per step; both weight operands stay VMEM-resident,
so the kernel targets block/projection-sized chains (the factory declines
shapes whose weights exceed the VMEM budget on real TPUs; interpret mode
has no such limit).

``fused_softmax_matmul`` — ``softmax(s, -1) @ v`` via the online-softmax
recurrence: the KV axis streams block by block through the sequential
last grid axis while the ``(m, l, acc)`` triple lives in VMEM scratch —
flash-attention's tail without the q·kᵀ head, exactly the shape of the
attention fusion groups ``gpt2_block`` produces after the softmax's
producer is a separate group task.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 contractions at full f32 precision on the MXU (not one bf16 pass).
HIGHEST = jax.lax.Precision.HIGHEST


def _block(dim: int, pref: int = 128) -> int:
    """Largest clean block: the MXU-aligned preferred size when it tiles
    ``dim`` exactly, otherwise the whole dim (single block)."""
    return pref if dim % pref == 0 else dim


# --------------------------------------------------------------------------
# matmul -> *ewise -> matmul
# --------------------------------------------------------------------------


def _chain_kernel(a_ref, w1_ref, w2_ref, o_ref, *, ew: Callable):
    h = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), w1_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    h = ew(h)                                    # fused elementwise tail(s)
    o_ref[...] = jax.lax.dot_general(
        h, w2_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def fused_matmul_chain(a: jax.Array, w1: jax.Array, w2: jax.Array, *,
                       ew: Callable | Sequence[Callable] = (),
                       block_m: int = 128,
                       interpret: bool = False) -> jax.Array:
    """``ew(a @ w1) @ w2`` as one Pallas kernel; ``ew`` is a single
    f32-block→f32-block callable or a sequence applied in order (empty =
    bare matmul chain)."""
    M, K = a.shape
    K2, N1 = w1.shape
    N12, N2 = w2.shape
    assert K == K2 and N1 == N12, (a.shape, w1.shape, w2.shape)
    fns = [ew] if callable(ew) else list(ew)

    def apply_ew(h):
        for f in fns:
            h = f(h)
        return h

    bm = min(_block(M, block_m), M)
    kernel = functools.partial(_chain_kernel, ew=apply_ew)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N1), lambda i: (0, 0)),
            pl.BlockSpec((N1, N2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N2), a.dtype),
        interpret=interpret,
        name="streamfuse_mmchain",
    )(a, w1, w2)


# --------------------------------------------------------------------------
# matmul -> *ewise gradient epilogue (backward-pass chains)
# --------------------------------------------------------------------------


def _grad_chain_kernel(*refs, ew: Callable):
    a_ref, w_ref, o_ref = refs[0], refs[1], refs[-1]
    extras = [r[...].astype(jnp.float32) for r in refs[2:-1]]
    h = jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = ew(h, *extras).astype(o_ref.dtype)


def fused_matmul_grad(a: jax.Array, w: jax.Array, *extras: jax.Array,
                      ew: Callable, block_m: int = 128,
                      interpret: bool = False) -> jax.Array:
    """``ew(a @ w, *extras)`` as one Pallas kernel — the backward-pass
    sibling of :func:`fused_matmul_chain`: a cotangent matmul whose
    gradient epilogue (``relu_grad``/``gelu_grad``/``softmax_grad`` plus
    plain elementwise) fuses onto the VMEM row-block instead of
    round-tripping through HBM.  ``extras`` are the epilogue's residual
    operands, each ``(M, N)`` and streamed with the same row-blocking as
    the output; ``softmax_grad``'s row reduction is exact because blocks
    span full rows."""
    M, K = a.shape
    K2, N = w.shape
    assert K == K2, (a.shape, w.shape)
    assert all(e.shape == (M, N) for e in extras), (
        [e.shape for e in extras], (M, N))
    bm = min(_block(M, block_m), M)
    kernel = functools.partial(_grad_chain_kernel, ew=ew)
    return pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
        ] + [pl.BlockSpec((bm, N), lambda i: (i, 0)) for _ in extras],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret,
        name="streamfuse_mmgrad",
    )(a, w, *extras)


# --------------------------------------------------------------------------
# softmax -> matmul (online-softmax streaming tail)
# --------------------------------------------------------------------------


def _softmax_mm_kernel(s_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = s_ref[...].astype(jnp.float32)           # (bm, bk)
    v = v_ref[...].astype(jnp.float32)           # (bk, N)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def fused_softmax_matmul(s: jax.Array, v: jax.Array, *,
                         block_m: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """``softmax(s, axis=-1) @ v`` as one streaming Pallas kernel.
    ``s``: (M, K); ``v``: (K, N).  The K axis iterates on the sequential
    last grid dimension, so the softmax normalizer is the online
    recurrence and the probability matrix never materializes."""
    M, K = s.shape
    K2, N = v.shape
    assert K == K2, (s.shape, v.shape)
    bm = min(_block(M, block_m), M)
    bk = min(_block(K, block_k), K)
    grid = (M // bm, K // bk)
    kernel = functools.partial(_softmax_mm_kernel, nk=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), s.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, N), jnp.float32),
        ],
        interpret=interpret,
        name="streamfuse_softmaxmm",
    )(s, v)
