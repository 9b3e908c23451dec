"""RG-LRU linear-recurrence Pallas kernel.

The recurrence h_t = a_t ⊙ h_{t-1} + b_t is FIFO-native (DESIGN.md §4):
each (a_t, b_t) element is consumed once, in order, and each h_t emitted
once.  The kernel streams time-chunks through VMEM; the carried state
h (B, D) lives in VMEM scratch and persists across the sequential grid —
the paper's temporary accumulator at sequence scale.

Within a chunk the scan runs as a fori_loop over time with the channel
dim vectorized on the VPU (on TPU: (8, 128)-tiled (B, D) updates).

Grid: (S / chunk,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, chunk: int):
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a_t = a_ref[:, t, :].astype(jnp.float32)
        b_t = b_ref[:, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        o_ref[:, t, :] = h.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])


def rglru_scan(a: jax.Array, b: jax.Array, *, chunk: int = 128,
               interpret: bool = False) -> jax.Array:
    """a, b: (B, S, D) -> h: (B, S, D) with h_t = a_t·h_{t-1} + b_t."""
    B, S, D = a.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(S // chunk,),
        in_specs=[
            pl.BlockSpec((B, chunk, D), lambda ci: (0, ci, 0)),
            pl.BlockSpec((B, chunk, D), lambda ci: (0, ci, 0)),
        ],
        out_specs=pl.BlockSpec((B, chunk, D), lambda ci: (0, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((B, D), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b)
