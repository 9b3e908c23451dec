"""Jit'd public wrapper for the SSD chunk-state kernel."""

from __future__ import annotations

from functools import partial

import jax

from ..common import kernel_mode
from .ref import ssd_chunk_scan_ref
from .ssd import ssd_chunk_scan


@partial(jax.jit, static_argnames=("use_kernel",))
def ssd_states(states, decay, *, use_kernel: bool = True):
    if not use_kernel:
        return ssd_chunk_scan_ref(states, decay)
    return ssd_chunk_scan(states, decay, interpret=kernel_mode() != "pallas")
