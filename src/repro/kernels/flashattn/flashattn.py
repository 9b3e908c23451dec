"""Streaming flash-attention Pallas kernel (TPU target).

CODO tie-in: the online-softmax recurrence is the paper's
reduction-operation rewriting (Fig. 5) on the softmax×V chain — KV is the
reduction dimension, the (m, l, acc) triple lives in VMEM scratch (the
"temporary array"), and the output tile is emitted exactly once, as early
as possible (when the last KV block for this Q tile retires).  The KV
stream through VMEM is the FIFO; the Pallas grid pipeline's double
buffering of the next KV block is the ping-pong stage of Fig. 1 — both
patterns in one kernel.

Grid: (B·Hq, Sq/bq, Sk/bk) — the last axis iterates sequentially on TPU,
so scratch persists across KV blocks.  GQA is expressed in the k/v
index_map (q-head b maps to kv-head b // group).  Causal + sliding-window
masks are built from block coordinates.

MXU alignment: bq/bk multiples of 128 (lane), hd is the contraction dim.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 contractions at full f32 precision on the MXU (not one bf16 pass).
HIGHEST = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd).  Returns (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * Hq, Sq, hd)
    kf = k.reshape(B * Hkv, Sk, hd)
    vf = v.reshape(B * Hkv, Sk, hd)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki, _G=G: (b // _G, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, qi, ki, _G=G: (b // _G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flashattn_mha",
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, hd)
