"""Jit'd public wrapper for the blocked matmul kernel."""

from __future__ import annotations

from functools import partial

import jax

from ..common import kernel_mode
from .matmul import matmul
from .ref import matmul_ref


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "use_kernel"))
def mm(a, b, *, block_m: int = 128, block_n: int = 128, block_k: int = 128,
       use_kernel: bool = True):
    if not use_kernel:
        return matmul_ref(a, b)
    return matmul(a, b, block_m=block_m, block_n=block_n, block_k=block_k,
                  interpret=kernel_mode() != "pallas")
