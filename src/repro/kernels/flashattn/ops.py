"""Jit'd public wrapper for the flash-attention kernel.

On TPU the Pallas kernel runs compiled; everywhere else it runs in
interpret mode (the kernel body executes in Python on CPU) so the same
code path is validated by the test sweep.
"""

from __future__ import annotations

from functools import partial

import jax

from ..common import kernel_mode
from .flashattn import flash_attention
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "use_kernel"))
def flash_attn(q, k, v, *, causal: bool = True, window: int = 0,
               block_q: int = 128, block_k: int = 128,
               use_kernel: bool = True):
    """Dispatch: Pallas kernel (compiled on TPU / interpreted elsewhere)."""
    if not use_kernel:
        return attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=kernel_mode() != "pallas")
