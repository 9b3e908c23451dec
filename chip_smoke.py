#!/usr/bin/env python3
"""Chip smoke test: CODO's main path on a TPU at GPT-2-medium width.

    python chip_smoke.py              # one chip: forward, train, serve
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

The one-chip run drives ``codo.compile`` -> six passes -> cost-gated
routing -> ``lower()`` -> execute through the entry points a user calls
(``codo.compile``, ``CompiledProgram``, ``CompiledTrainStep``,
``ServingRuntime``) at d_model 1024 and sequence 1024 (GPT-2 medium,
``configs/gpt2_medium.py``: one block, random weights from ``--seed``).

* forward: the routing table, at least one group routed in ``pallas``
  mode with a ``tpu_custom_call`` in the compiled program, and the output
  against a plain float32 ``jax.numpy`` block under
  ``jax.default_matmul_precision("highest")``;
* train: three ``CompiledTrainStep.step()`` calls with a finite loss, and
  ``CompiledTrainStep.verify`` against eager ``jax.value_and_grad``;
* serve: an in-process ``ServingRuntime`` answers 8 requests, each equal
  to the same design run on its own.

``--chips 4`` compiles the same block with ``mesh=`` a 2x2 ``MeshSpec``
under ``sharding_strategy="tp"`` and ``"auto"``, checks each against the
one-chip program with ``verify_sharding`` and that four distinct devices
hold the output.

The script refuses to run without a TPU (no CPU fallback), and when any
switch that changes routing is set in the environment.  jax's persistent
compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` or else
``<checkout>/.jax_cache``; a second run reports cache hits.  The last
line of standard output is one JSON object, printed only when every
phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

S, D = 1024, 1024       # GPT-2 medium: n_ctx 1024, d_model 1024
N_HEADS = 16            # gpt2_block_fn scales scores by 1/sqrt(D // 16)

# Switches that change which kernels run or how the gate prices them;
# routing here must come from committed code alone.
REFUSED_ENV = ("CODO_PALLAS_INTERPRET", "CODO_FORCE_PALLAS",
               "CODO_DISABLE_PALLAS", "CODO_BACKEND")

# Tolerances, each with its reason (printed beside every comparison).
F32_TOL = dict(rtol=1e-4, atol=1e-4)
F32_WHY = ("f32 at HIGHEST precision on both sides; contractions of length "
           "<= 4096 summed in a different order by the kernels (online "
           "softmax, row blocks) than by XLA: sqrt(4096)*2^-24 = 4e-6 per "
           "dot, compounded through three chained contractions and a "
           "softmax; a wrong program errs by O(1)")
SERVE_WHY = ("the batched graph (4, S, D) is another design than the "
             "one-request graph: it may route differently (the VMEM guard "
             "sees stacked K/V) and sum in another order; otherwise as "
             "for the forward phase")

DECISION_WHY = {
    "declined": "factory declined at build time: resident operands exceed "
                "the VMEM budget",
    "predicted-loss": "cost gate predicted the kernel no faster",
    "sharded": "a collective lands inside the chain",
}


class SmokeError(RuntimeError):
    """A phase failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# --------------------------------------------------------------------------
# Device
# --------------------------------------------------------------------------


def device_phase(chips: int):
    set_env = [k for k in REFUSED_ENV if os.environ.get(k)]
    check(not set_env, f"refusing to run with {set_env} set: routing must "
                       f"come from committed code only")
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU chip: jax.devices()[0].platform is {d0.platform!r}; "
          f"this smoke runs on a TPU and has no CPU fallback")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU devices, jax sees {len(devs)}")
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} jax={jax.__version__}")
    return d0, len(devs)


def import_repro():
    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SmokeError(f"the repro package is not next to this script "
                         f"({src}): {e}") from e
    from repro.core.cache import enable_jax_cache
    print(f"jax compilation cache: {enable_jax_cache()}")
    print("codo compile cache: off (cache=None: every pass runs)")


# --------------------------------------------------------------------------
# Data and the plain reference
# --------------------------------------------------------------------------


def block_weights(seed: int) -> dict:
    """wq, wk, wv, wo (D, D), w1 (D, 4D), w2 (4D, D), scaled by fan-in."""
    rng = np.random.default_rng(seed)
    shapes = (("wq", (D, D)), ("wk", (D, D)), ("wv", (D, D)),
              ("wo", (D, D)), ("w1", (D, 4 * D)), ("w2", (4 * D, D)))
    return {n: (rng.standard_normal(s) / math.sqrt(s[0])).astype(np.float32)
            for n, s in shapes}


def bind_block_weights(program, weights: dict) -> dict:
    """Map the reference weights onto the traced block's weight buffers,
    which ``gpt2_block_fn`` creates in the order q, k, v, proj, fc1, fc2."""
    bufs = list(program.source.weights())
    roles = ("wq", "wk", "wv", "wo", "w1", "w2")
    check([tuple(b.shape) for b in bufs] ==
          [weights[r].shape for r in roles],
          f"unexpected block weights {[(b.name, b.shape) for b in bufs]}")
    return {b.name: weights[r] for b, r in zip(bufs, roles)}


def gpt2_block_ref(x, w):
    """One GPT-2 block as gpt2_block_fn writes it, in plain jax.numpy."""
    import jax
    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    p = jax.nn.softmax((q @ k.T) / math.sqrt(D // N_HEADS), axis=-1)
    h = (p @ v) @ w["wo"] + x
    return jax.nn.gelu(h @ w["w1"], approximate=True) @ w["w2"] + h


def reference(x, w):
    import jax
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(gpt2_block_ref)(x, w))


def compare(name: str, pairs, tol: dict, why: str) -> None:
    """Hold every (got, want) pair to ``tol``; print the worst error with
    the tolerance and its reason."""
    err = rel = 0.0
    for got, want in pairs:
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                       f"{want.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
        e = float(np.max(np.abs(got - want)))
        err = max(err, e)
        rel = max(rel, e / max(float(np.max(np.abs(want))), 1e-30))
    print(f"{name}: max abs err {err:.3e} (rel to max {rel:.3e}) within "
          f"rtol={tol['rtol']:g} atol={tol['atol']:g}: {why}")


# --------------------------------------------------------------------------
# Compile timing (jax persistent cache)
# --------------------------------------------------------------------------


class CacheEvents:
    """Counts jax's persistent-cache hit/miss events."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.counts: Counter = Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        self.counts[event] += 1

    def snapshot(self) -> tuple[int, int]:
        return self.counts[self.HIT], self.counts[self.MISS]


def timed_compile(fn, env, events: CacheEvents, label: str):
    h0, m0 = events.snapshot()
    t0 = time.perf_counter()
    compiled = fn.lower(env).compile()
    secs = time.perf_counter() - t0
    h1, m1 = events.snapshot()
    print(f"compile {label}: {secs:.3f} s (persistent cache: {h1 - h0} "
          f"hits, {m1 - m0} misses)")
    return compiled


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------


def routing_table(lowered) -> int:
    """Print the routing table (every group a pattern matched, with its
    routes and rejections); return the routed-kernel count."""
    routed = 0
    matched = [g for g in lowered.groups if g.routes or g.rejected]
    print(f"  {len(lowered.groups)} fusion groups, "
          f"{len(lowered.groups) - len(matched)} without a pattern match")
    for g in matched:
        print(f"  group {g.gid}: {g.kernel} decision={g.decision} "
              f"tasks={len(g.tasks)}")
        for r in g.routes:
            routed += 1
            print(f"    routed   {r.kernel} [{r.decision}] "
                  f"{r.tasks[0]}..{r.tasks[-1]} predicted cycles "
                  f"{r.predicted_routed_cycles:.0f} vs generic "
                  f"{r.predicted_generic_cycles:.0f}")
        for r in g.rejected:
            why = DECISION_WHY.get(r.decision, r.decision)
            print(f"    rejected {r.kernel} [{r.decision}] "
                  f"{r.tasks[0]}..{r.tasks[-1]}: {why}")
    return routed


def forward_phase(seed: int, events: CacheEvents):
    import jax
    import repro.api as codo
    from repro.kernels.common import VMEM_BUDGET_BYTES, kernel_mode
    from repro.models.dataflow_models import gpt2_block_fn

    t0 = time.perf_counter()
    program = codo.compile(gpt2_block_fn, (S, D), cache=None)
    print(f"forward: codo.compile {program.graph.name} ({S}, {D}) in "
          f"{time.perf_counter() - t0:.3f} s, modeled speedup "
          f"{program.speedup:.1f}x")
    mode = kernel_mode()
    check(mode == "pallas", f"kernel_mode() is {mode!r}, not 'pallas'")
    weights = block_weights(seed)
    program.bind(**bind_block_weights(program, weights))
    x = np.random.default_rng(seed + 1).standard_normal((S, D)).astype(
        np.float32)

    lowered = program.lower(jit=True)
    print(f"forward routing (mode {mode}, VMEM budget "
          f"{VMEM_BUDGET_BYTES >> 20} MiB):")
    routed = routing_table(lowered)
    check(routed >= 1, "no group routed to a Pallas kernel")

    env = program.make_env(x)
    compiled = timed_compile(lowered.fn, env, events, "cold")
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"forward: {routed} routed kernel(s), {kernels} tpu_custom_call "
          f"in the compiled program")
    check(kernels >= routed, f"{routed} routes recorded but only {kernels} "
                             f"tpu_custom_call in the program")
    jax.clear_caches()
    timed_compile(lowered.fn, env, events,
                  "warm (same process, in-memory caches cleared)")

    t0 = time.perf_counter()
    got = np.asarray(program(x))
    print(f"forward: first call through CompiledProgram "
          f"{time.perf_counter() - t0:.3f} s")
    compare("forward vs float32 jnp reference",
            [(got, reference(x, weights))], F32_TOL, F32_WHY)
    return program


def train_phase(seed: int):
    import repro.api as codo
    from repro.models.dataflow_models import gpt2_block_loss_fn

    t0 = time.perf_counter()
    step = codo.compile(gpt2_block_loss_fn, (S, D), (S, D), grad=True,
                        cache=None)
    print(f"train: codo.compile(grad=True) in "
          f"{time.perf_counter() - t0:.3f} s: {step!r}")
    for tag, prog in (("forward", step.forward), ("backward", step.backward),
                      ("update", step.update)):
        low = prog.lower(jit=True)
        print(f"train {tag} routing:")
        routing_table(low)
    rng = np.random.default_rng(seed + 2)
    x = rng.standard_normal((S, D)).astype(np.float32)
    target = rng.standard_normal((S, D)).astype(np.float32)
    params = step.init_params()
    opt_state = step.init_opt_state(params)
    for i in range(3):
        t0 = time.perf_counter()
        params, opt_state, metrics = step.step(params, opt_state, x, target)
        loss = float(metrics["loss"])
        print(f"train step {i}: loss {loss:.6f} grad_norm "
              f"{float(metrics['grad_norm']):.6f} lr "
              f"{float(metrics['lr']):.3e} ({time.perf_counter() - t0:.3f} s)")
        check(math.isfinite(loss), f"train step {i}: loss {loss}")
    step.verify(x, target, params=params)
    print("train: CompiledTrainStep.verify vs eager jax.value_and_grad "
          "passed (rtol=2e-3 atol=1e-4, the documented fp32 band: loss "
          "and gradients reassociate across contractions)")


def serve_phase(program, seed: int):
    from repro import api as codo
    from repro.core.cache import CompileCache
    from repro.serving.runtime import ServeConfig, ServingRuntime

    doc = program.export(weights=True)
    rng = np.random.default_rng(seed + 3)
    xs = [rng.standard_normal((S, D)).astype(np.float32) for _ in range(8)]
    cfg = ServeConfig(workers=0, max_batch=4, batch_window_ms=5.0)
    with ServingRuntime(cfg, cache=CompileCache()) as rt:
        t0 = time.perf_counter()
        rt.add_model("gpt2_block", doc)
        print(f"serve: model loaded and warmed in "
              f"{time.perf_counter() - t0:.3f} s")
        rt.pause()                       # fill the window deterministically
        futs = [rt.submit("gpt2_block", **{program.input_names[0]: x})
                for x in xs]
        t0 = time.perf_counter()
        rt.resume()
        outs = [f.result(timeout=600) for f in futs]
        print(f"serve: {len(outs)} requests resolved in "
              f"{time.perf_counter() - t0:.3f} s")
        stats = rt.stats.snapshot()
    print(f"serve: {stats['batches']} dispatches, "
          f"{stats['batched_requests']} batched requests, "
          f"{stats['fallback_requests']} per-request")
    check(stats["completed"] == 8 and stats["failed"] == 0,
          f"serve stats {stats}")
    check(stats["batched_requests"] == 8,
          f"expected all 8 requests coalesced, stats {stats}")
    single = codo.load(doc)
    out_name = single.output_names[0]
    compare(f"serve: {len(outs)} requests vs the design run alone",
            [(out[out_name], single(x)) for x, out in zip(xs, outs)],
            F32_TOL, SERVE_WHY)


def sharded_phase(seed: int, n_devices: int):
    import repro.api as codo
    from repro.core.lowering import verify_sharding
    from repro.distributed.plan import MeshSpec
    from repro.models.dataflow_models import gpt2_block_fn

    mesh = MeshSpec((("data", 2), ("model", 2)))
    weights = block_weights(seed)
    x = np.random.default_rng(seed + 1).standard_normal((S, D)).astype(
        np.float32)
    want = reference(x, weights)
    for strategy in ("tp", "auto"):
        t0 = time.perf_counter()
        program = codo.compile(gpt2_block_fn, (S, D), cache=None, mesh=mesh,
                               sharding_strategy=strategy)
        program.bind(**bind_block_weights(program, weights))
        plan = program.sharding
        print(f"sharded {strategy}: plan {plan.strategy} on "
              f"{dict(mesh.axes)}, {len(plan.steps)} collectives, compiled "
              f"in {time.perf_counter() - t0:.3f} s")
        env = program.make_env(x)
        verify_sharding(program.compiled, plan, env)
        print(f"sharded {strategy}: verify_sharding vs the one-chip program "
              f"passed (rtol=1e-4 atol=5e-5: psum tree-reduces device "
              f"partials and local-shape matmuls contract in another order)")
        out = program(x)
        shards = sorted((s.device.id, str(s.index)) for s in
                        out.addressable_shards)
        for dev_id, index in shards:
            print(f"sharded {strategy}: output shard on device {dev_id}: "
                  f"{index}")
        held = {d for d, _ in shards}
        check(len(held) == n_devices == 4,
              f"output held by devices {sorted(held)}, want 4 distinct")
        compare(f"sharded {strategy} vs float32 jnp reference",
                [(out, want)], F32_TOL, F32_WHY)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: forward, train, serve on one chip; 4: only the "
                         "sharded phase on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)
    try:
        dev, count = device_phase(args.chips)
        import_repro()
        if args.chips == 4:
            t0 = time.perf_counter()
            sharded_phase(args.seed, count)
            print(f"phase sharded passed in {time.perf_counter() - t0:.1f} s")
        else:
            events = CacheEvents()
            t0 = time.perf_counter()
            program = forward_phase(args.seed, events)
            print(f"phase forward passed in {time.perf_counter() - t0:.1f} s")
            t0 = time.perf_counter()
            train_phase(args.seed)
            print(f"phase train passed in {time.perf_counter() - t0:.1f} s")
            t0 = time.perf_counter()
            serve_phase(program, args.seed)
            print(f"phase serve passed in {time.perf_counter() - t0:.1f} s")
    except (SmokeError, AssertionError) as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
