"""Blocked matmul Pallas kernel — the paper's reduction rewriting (Fig. 5)
in its purest form.

The naive loop writes ``out[m,n]`` once per k iteration (the access-count
mismatch of §IV-B).  The rewritten kernel accumulates the (bm, bn) tile in
a VMEM f32 scratch across the sequential k grid axis and emits it exactly
once when the last k block retires — early, just-in-time, FIFO-clean.

Grid (M/bm, N/bn, K/bk); blocks MXU-aligned (multiples of 128 on the
lane dims; bm on the sublane dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _mm_kernel(a_ref, b_ref, o_ref, acc, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: bool = False) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape)
    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(_mm_kernel, nk=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul",
    )(a, b)
