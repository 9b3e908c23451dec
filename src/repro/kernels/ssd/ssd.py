"""Mamba-2 SSD chunk-state Pallas kernel.

The inter-chunk recurrence h_{c+1} = decay_c · h_c + state_c over per-chunk
states (B, H, P, N) — the sequential backbone of the SSD algorithm — is
another pure linear stream: FIFO-native.  The intra-chunk dense blocks are
MXU matmuls best left to XLA; this kernel owns the sequential part that
XLA would otherwise express as a scan with HBM round-trips per step.

Per grid step c the kernel consumes (state_c, decay_c), updates the VMEM-
resident running state, and emits the *carried-in* state h_c (what the
intra-chunk off-diagonal term consumes) — emitted exactly once, before the
update, i.e. as early as possible (Fig. 5 discipline).

Grid: (n_chunks,); state shaped (B·H, P, N) for (sublane, lane) tiling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(states_ref, decay_ref, prev_ref, h_scr):
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    # emit the carried-in state for this chunk (used by the off-diagonal
    # output term), then fold in this chunk's contribution.
    prev_ref[0] = h_scr[...].astype(prev_ref.dtype)
    dec = decay_ref[0].astype(jnp.float32)         # (BH, 1, 1) broadcastable
    st = states_ref[0].astype(jnp.float32)         # (BH, P, N)
    h_scr[...] = h_scr[...] * dec + st


def ssd_chunk_scan(states: jax.Array, decay: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """states: (nc, BH, P, N); decay: (nc, BH, 1, 1).
    Returns h_prev: (nc, BH, P, N) — the state carried *into* each chunk."""
    nc, BH, P, N = states.shape
    return pl.pallas_call(
        _ssd_kernel,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((1, BH, P, N), lambda c: (c, 0, 0, 0)),
            pl.BlockSpec((1, BH, 1, 1), lambda c: (c, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BH, P, N), lambda c: (c, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, BH, P, N), states.dtype),
        scratch_shapes=[pltpu.VMEM((BH, P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(states, decay)
