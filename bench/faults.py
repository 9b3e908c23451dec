"""Faults planted under the timed path, to show that ``correct`` catches
them: each is a context manager that patches one entry point of the
program for as long as it is open.  Used by the tests on the CPU and by
``control.py`` on the chip; the benchmark's own runs never open one.

* ``answer_altered`` — one element of each forward answer is off by one;
* ``served_answer_altered`` — the same, in the serving runtime's batches;
* ``state_unchanged`` — a train step returns the state it was given;
* ``half_batch`` — a train step sees only the first half of the rows
  (the second half repeats them), so its mean is over the rest.
"""

from __future__ import annotations

from contextlib import contextmanager


@contextmanager
def _patched(owner, name: str, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def _alter(out):
    return out.at[(0,) * out.ndim].add(1.0)


def answer_altered():
    from repro.api import CompiledProgram

    def make(orig):
        def call(self, *a, **k):
            return _alter(orig(self, *a, **k))
        return call
    return _patched(CompiledProgram, "__call__", make)


def served_answer_altered():
    import numpy as np
    from repro.serving import runtime

    def make(orig):
        def run_batch(*a, **k):
            results = orig(*a, **k)
            for r in results:
                for name, v in r.items():
                    v = np.array(v)
                    v[(0,) * v.ndim] += 1.0
                    r[name] = v
            return results
        return run_batch
    return _patched(runtime, "_run_batch", make)


def state_unchanged():
    from repro.api import CompiledTrainStep

    def make(orig):
        def step(self, params, opt_state, *a, **k):
            _, _, metrics = orig(self, params, opt_state, *a, **k)
            return params, opt_state, metrics
        return step
    return _patched(CompiledTrainStep, "step", make)


def half_batch():
    import jax.numpy as jnp
    from repro.api import CompiledTrainStep

    def make(orig):
        def step(self, params, opt_state, *arrays, **k):
            half = [jnp.concatenate([a[:a.shape[0] // 2]] * 2) for a in arrays]
            return orig(self, params, opt_state, *half, **k)
        return step
    return _patched(CompiledTrainStep, "step", make)
