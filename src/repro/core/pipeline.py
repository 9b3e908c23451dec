"""Task-level pipeline executor — CODO's dataflow execution on a chip mesh.

The paper's accelerator overlaps *tasks* on one FPGA.  At pod scale the
same schedule maps onto pipeline parallelism: the scheduler's balanced
stages (schedule.assign_stages) each own a mesh slice, activations stream
stage→stage over ICI via ``collective_permute`` (= the FIFO), and
microbatches fill the pipeline exactly like Fig. 1(a)'s elements.

Implemented with ``shard_map`` over a ``stage`` axis:

* every device holds its stage's weights only,
* a ``jax.lax.scan`` over (num_microbatches + num_stages - 1) ticks runs
  the classic GPipe fill/steady/drain schedule,
* each tick: compute your stage on the held activation, then
  ``ppermute`` the result one stage forward (overlap: the permute of tick
  t and the compute of tick t+1 pipeline through XLA's async collectives).

The stage functions must be shape-preserving (activation (mb, ...) in/out),
which the transformer-block stages used in tests/examples satisfy.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@dataclass
class PipelineSchedule:
    num_stages: int
    num_microbatches: int

    @property
    def ticks(self) -> int:
        return self.num_microbatches + self.num_stages - 1

    @property
    def bubble_fraction(self) -> float:
        return (self.num_stages - 1) / self.ticks


def pipeline_fn(stage_fns: Sequence[Callable[[Any, jax.Array], jax.Array]],
                mesh: Mesh, axis: str = "stage"):
    """Build a pipelined forward: (stacked_params, microbatched_x) -> y.

    ``stacked_params`` is a pytree whose leaves have a leading ``stage``
    dim (one slice per stage, sharded over ``axis``).  ``x`` is
    (num_microbatches, mb, ...) with outputs of the same shape.
    """
    num_stages = mesh.shape[axis]

    def per_device(params, x):  # params: this stage's slice; x: all microbatches
        params = jax.tree.map(lambda a: a[0], params)  # drop the stage dim
        sid = jax.lax.axis_index(axis)
        nmb = x.shape[0]
        ticks = nmb + num_stages - 1
        buf = jnp.zeros_like(x[0])
        outs = jnp.zeros_like(x)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, nmb - 1)
            inject = x[mb_idx]
            cur = jnp.where(sid == 0, inject, buf)
            y = _apply_stage(params, cur, sid)
            # last stage emits microbatch (t - num_stages + 1)
            out_idx = jnp.clip(t - (num_stages - 1), 0, nmb - 1)
            valid = jnp.logical_and(sid == num_stages - 1,
                                    t >= num_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outs)
            # stream forward one stage (the inter-stage FIFO)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % num_stages) for i in range(num_stages)])
            return (nxt, outs), None

        def _apply_stage(params, h, sid):
            # dispatch to this stage's function via switch (stage fns may
            # differ structurally)
            branches = [partial(lambda f, p, a: f(p, a), f) for f in stage_fns]
            return jax.lax.switch(sid, branches, params, h)

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage populated outs; psum replicates it (all other
        # stages contribute zeros) so out_specs=P() holds
        return jax.lax.psum(outs, axis)

    return jax.jit(
        jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axis), P()),     # params sharded by stage; x replicated
            out_specs=P(),
            check_vma=False,
        ))


def reference_serial(stage_fns, params_stacked, x):
    """Oracle: run stages sequentially over all microbatches (no pipeline)."""
    nmb = x.shape[0]
    outs = []
    for m in range(nmb):
        h = x[m]
        for s, f in enumerate(stage_fns):
            p = jax.tree.map(lambda a: a[s], params_stacked)
            h = f(p, h)
        outs.append(h)
    return jnp.stack(outs)
