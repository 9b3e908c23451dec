"""Each configuration's plain reference agrees with ``codo.compile`` of its
block at a small size, with the routed kernels' Pallas bodies run in
interpret mode."""

import numpy as np
import pytest

import harness
import refops
from conftest import BENCH

# Sizes at which the cost gate routes each block's kernels on the CPU:
# flashattn and the FFN chain at S=128; five streamfuse convs at H=96.
CASES = {"gpt2-medium-block": ({"n_embd": 64, "n_head": 16}, [(128, 64)]),
         "resnet18-224": ({"embedding_size": 64, "hidden_sizes": [64, 128, 256, 512],
                           "depths": [2, 2, 2, 2]}, [(1, 3, 96, 96)])}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_agrees_with_compiled_block(name, monkeypatch):
    import jax
    import repro.api as codo
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    cfg, shapes = CASES[name]
    model = harness.load_module(BENCH / "configs" / f"{name}.py")
    program = codo.compile(model.block, *shapes, cache=None)
    assert harness.routing(program.lower()), "no kernel routed at this size"
    wbufs = list(program.source.weights())
    ws = harness.normal_arrays(harness.prng_key(7, 1),
                               [b.shape for b in wbufs], fan_in=True)
    xs = harness.normal_arrays(harness.prng_key(7, 2), shapes, fan_in=False)
    program.bind(**{b.name: w for b, w in zip(wbufs, ws)})
    got = np.asarray(program(*xs))
    want = jax.jit(lambda w, *x: model.reference(cfg, refops.at("highest"),
                                                 w, *x))(ws, *xs)
    assert harness.rel_err(got, want) < 1e-5
