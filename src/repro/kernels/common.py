"""Shared backend-dispatch helpers for kernel-pattern registration.

Every kernel family resolves the same three questions when its routing
factory builds an executable step — which backend mode to run in, whether
the resident operands fit VMEM, and whether the chain is all-f32.  This
module is the one place those answers live, for the streamfuse,
flashattn, rglru and ssd pattern modules alike.

Modes:

* ``"pallas"``    — compiled Pallas kernel (TPU hosts, always);
* ``"interpret"`` — the Pallas kernel body in interpret mode, forced by
  ``CODO_PALLAS_INTERPRET=1`` on non-TPU hosts (how CI exercises the true
  kernel path on CPU runners);
* ``"reference"`` — the kernel's fused jnp reference under one jit
  (CPU/GPU hosts): the same fusion decision, carried by XLA.
"""

from __future__ import annotations

import numpy as np

from ..core.routing import pallas_interpret_forced

# Resident-operand budget for compiled (TPU) kernels; interpret/reference
# modes are unconstrained.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def kernel_mode() -> str:
    """'pallas' (compiled, TPU), 'interpret' (forced), or 'reference'.
    On a TPU backend the answer is always 'pallas':
    :func:`~repro.core.routing.pallas_interpret_forced` ignores the
    interpret flag there."""
    if pallas_interpret_forced():
        return "interpret"
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def vmem_ok(*shapes) -> bool:
    return sum(int(np.prod(s)) for s in shapes) * 4 <= VMEM_BUDGET_BYTES


def all_f32(graph, *bufs) -> bool:
    return all(np.dtype(graph.buffers[b].dtype) == np.float32 for b in bufs)


def pow2_block(n: int, cap: int = 128) -> int:
    """Largest power-of-two divisor of ``n``, capped at ``cap`` — the
    block size the Pallas kernels' divisibility asserts always accept."""
    b = 1
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b
