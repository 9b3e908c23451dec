"""Jit'd public wrapper for the RG-LRU recurrence kernel."""

from __future__ import annotations

from functools import partial

import jax

from ..common import kernel_mode
from .ref import rglru_ref
from .rglru import rglru_scan


@partial(jax.jit, static_argnames=("chunk", "use_kernel"))
def rglru(a, b, *, chunk: int = 128, use_kernel: bool = True):
    if not use_kernel:
        return rglru_ref(a, b)
    return rglru_scan(a, b, chunk=chunk, interpret=kernel_mode() != "pallas")
