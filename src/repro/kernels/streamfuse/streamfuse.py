"""Fused pad→conv3×3→relu streaming kernel — the paper's motivating
example (Fig. 2) as one Pallas kernel.

The three tasks communicate through VMEM instead of HBM: the "FIFO"
between Padding and Conv2D is a set of K row-shifted BlockSpec views of
the padded input — each grid step streams rows [h, h+K) into VMEM, which
is exactly the (K-1)-row **line buffer** of Fig. 7 realized by the grid
pipeline (block dim 1 on the row axis makes the block index an element
index, so consecutive steps re-fetch K-1 rows the pipeline already holds).
The Conv→ReLU FIFO is a register value; the kh·kw·ci **window buffer** is
the VMEM working set of the K·K accumulated dots below.

Layout: the kernel streams ``(N, H+2p, C, W+2p)`` rows (the padded input
with rows ahead of channels), so each row block ``(1, 1, C, W+2p)`` keeps
the full (channel, column) plane in its last two dims — Mosaic tiles the
last two block dims by (8, 128) unless they span the whole array dim, and
a 1-row block on the row axis of NCHW does neither.  Weights arrive as
``(K, K, CO, C)`` so each tap is one clean ``(CO, C)`` slab.

Grid: (N, H) — one output row per step; weights stay VMEM-resident.  The
grid pipeline double-buffers the next row while the MXU works on the
current one: Fig. 1's ping-pong and FIFO in one mechanism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def _fused_kernel(*refs, W: int, K: int):
    x_rows = refs[:K]           # K refs, each (1, 1, C, Wp)
    w_ref = refs[K]             # (K, K, CO, C)
    o_ref = refs[K + 1]         # (1, 1, CO, W)
    acc = None
    for kh in range(K):
        row = x_rows[kh][0, 0].astype(jnp.float32)          # (C, Wp)
        for kw in range(K):                                  # window taps
            tap = jax.lax.dot_general(
                w_ref[kh, kw].astype(jnp.float32), row[:, kw:kw + W],
                (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)
            acc = tap if acc is None else acc + tap
    o_ref[0, 0] = jnp.maximum(acc, 0.0).astype(o_ref.dtype)  # fused ReLU


def fused_pad_conv_relu(x: jax.Array, w: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """x: (N, C, H, W); w: (CO, C, K, K), stride 1, SAME padding.
    Returns relu(conv2d(pad(x), w)): (N, CO, H, W)."""
    N, C, H, W = x.shape
    CO, C2, K, K2 = w.shape
    assert C == C2 and K == K2 and K % 2 == 1
    p = K // 2
    xp = jnp.pad(x, ((0, 0), (0, 0), (p, p), (p, p))).transpose(0, 2, 1, 3)
    Wp = W + 2 * p

    def row_spec(dk: int) -> pl.BlockSpec:
        return pl.BlockSpec((1, 1, C, Wp),
                            lambda n, h, _dk=dk: (n, h + _dk, 0, 0))

    kernel = functools.partial(_fused_kernel, W=W, K=K)
    out = pl.pallas_call(
        kernel,
        grid=(N, H),
        in_specs=[row_spec(dk) for dk in range(K)] + [
            pl.BlockSpec((K, K, CO, C), lambda n, h: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CO, W), lambda n, h: (n, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H, CO, W), x.dtype),
        interpret=interpret,
        name="streamfuse_conv",
    )(*([xp] * K), w.transpose(2, 3, 0, 1))
    return out.transpose(0, 2, 1, 3)
