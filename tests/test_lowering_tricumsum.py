"""Long cumulative sums lowered as triangular matmuls.

A declarative ``cumsum`` task with an f32 operand scanned along an axis of
at least a lane (128) runs as ``tri_cumsum``: the axis cut into blocks,
each contracted with lower-triangular ones at ``HIGHEST``, each block's
carry an exclusive prefix of the block totals.  Shorter axes and other
dtypes keep the registry's ``jnp.cumsum``.
"""

import importlib.util
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as codo
from repro.core import frontend as F
from repro.core import lowering
from repro.core.lowering import (LOWER_CACHE_STATS, TRI_CUMSUM_BLOCK,
                                 clear_lower_cache, lower, takes_tri_cumsum,
                                 tri_cumsum, tri_cumsum_rule, verify_lowering)

ROOT = Path(__file__).resolve().parents[1]
EPS = np.finfo(np.float32).eps


@pytest.mark.parametrize("n,dtype,tri", [
    (6, np.float32, False),
    (127, np.float32, False),
    (128, np.float32, True),          # a lane
    (256, np.float32, True),          # granite's chunk
    (300, np.float32, True),          # not a multiple of a block
    (256, np.float16, False),
    (256, jnp.bfloat16, False),
    (256, np.float64, False),
    (256, np.int32, False),
])
def test_tri_cumsum_rule(n, dtype, tri):
    assert tri_cumsum_rule(n, dtype) is tri


@pytest.mark.parametrize("block", sorted({128, 256, TRI_CUMSUM_BLOCK}))
@pytest.mark.parametrize("shape,axis", [
    ((256, 3, 5), 0), ((3, 256, 5), 1), ((3, 5, 256), 2),
    ((300, 4), 0), ((2, 300, 3), 1), ((4, 300), 1),
    ((2, 6, 3), 1),                   # one short block
])
def test_tri_cumsum_matches_float64(shape, axis, block):
    """Within f32's bound for a sum of n terms in any order: (n - 1) eps
    times the running sum of magnitudes."""
    rng = np.random.default_rng(sum(shape) + axis + block)
    x = rng.standard_normal(shape).astype(np.float32)
    got = np.asarray(tri_cumsum(jnp.asarray(x), axis, block))
    want = np.cumsum(x.astype(np.float64), axis)
    assert got.shape == shape and got.dtype == np.float32
    n = shape[axis]
    bound = (n - 1) * EPS * np.cumsum(np.abs(x.astype(np.float64)), axis)
    assert np.all(np.abs(got - want) <= bound)


def _segment_steps(rng, nc=2, h=4, n=256):
    """granite's segment-sum operand: each row's step broadcast along the
    row, kept strictly below the diagonal; steps in [-30, 0], so the sums
    reach ~1.5e3 in magnitude."""
    steps = rng.uniform(-30.0, 0.0, (nc, h, n, 1)).astype(np.float32)
    below = np.tri(n, n, -1, dtype=bool)
    return np.where(below, np.broadcast_to(steps, (nc, h, n, n)),
                    np.float32(0))


@pytest.mark.parametrize("block", sorted({128, 256, TRI_CUMSUM_BLOCK}))
def test_segment_sums_exp_as_accurate_as_cumsum(block):
    x = _segment_steps(np.random.default_rng(3))
    want = np.cumsum(x.astype(np.float64), 2)
    assert np.abs(want).max() > 1e3
    got = np.asarray(tri_cumsum(jnp.asarray(x), 2, block), np.float64)
    ref = np.asarray(jnp.cumsum(jnp.asarray(x), 2), np.float64)

    def exp_err(seg):
        return np.abs(np.exp(seg) - np.exp(want)).max()

    # exp(seg) is at most 1: a few ulps of 1 over jnp.cumsum's error.
    assert exp_err(got) <= exp_err(ref) + 4 * EPS


def test_non_finite_spreads_over_its_block():
    """The documented difference: an inf makes its whole block (and the
    blocks after it) non-finite, where jnp.cumsum keeps the rows before
    it finite."""
    x = np.ones((2, 256), np.float32)
    x[0, 200] = np.inf
    got = np.asarray(tri_cumsum(jnp.asarray(x), 1, 128))
    assert np.isfinite(got[0, :128]).all()
    assert not np.isfinite(got[0, 128:]).any()
    np.testing.assert_array_equal(got[1], np.arange(1, 257))
    assert np.isfinite(np.asarray(jnp.cumsum(x, 1))[0, :200]).all()


def _scan_net(x):
    long = F.cumsum(x, 1)                              # (4, 256, 8) axis 1
    short = F.cumsum(F.reshape(x, (4, 8, 32, 8)), 2)   # 32 long
    seg = F.cumsum(F.causal_mask(F.broadcast_to(F.reshape(
        F.slice_(x, (0, 0, 0), (4, 256, 1)), (4, 256, 1)), (4, 256, 256)),
        diagonal=-1, fill=0.0), 1)                     # a segment sum
    return F.add(F.add(long, F.reshape(short, (4, 256, 8))),
                 F.reshape(F.slice_(seg, (0, 0, 0), (4, 256, 8)),
                           (4, 256, 8)))


@pytest.fixture(scope="module")
def scan_program():
    return codo.compile(_scan_net, (4, 256, 8), cache=None)


@pytest.mark.parametrize("which,tri", [("long", True), ("short", False),
                                       ("segment", True)])
def test_gate_on_graph_tasks(scan_program, which, tri):
    """Which of a small net's cumsum tasks take the triangular path, and
    the scope their step is emitted under."""
    graph = scan_program.graph
    sums = [t for t in graph.toposort() if t.spec.kind == "cumsum"]
    assert len(sums) == 3
    t = sums[["long", "short", "segment"].index(which)]
    assert takes_tri_cumsum(graph, t) is tri
    groups = lowering.fusion_groups(graph,
                                    scan_program.compiled.buffer_plan.impl)
    scopes = {n: scope for n, scope, _fn in
              lowering._build_steps(graph, groups, False)}
    assert scopes[t.name].endswith(".tricumsum" if tri else ".xla")


def test_lowered_net_matches_oracle_and_counts(scan_program):
    clear_lower_cache()
    lowered = lower(scan_program.compiled, jit=False,
                    use_registered_kernels=False)
    assert lowered.tri_cumsums == 2
    assert LOWER_CACHE_STATS["tri_cumsums"][lowered.name] == 2
    assert "2 triangular cumsums" in lowered.summary()
    assert "tri_cumsums=2" in repr(lowered)
    env = scan_program.make_env(np.random.default_rng(0).standard_normal(
        (4, 256, 8)).astype(np.float32))
    # f32 sums of 256 normal terms in another order: a few ulps of their
    # running magnitude (~200), where an output near 0 may have cancelled.
    verify_lowering(scan_program.source, scan_program.compiled, env,
                    atol=1e-4)
    clear_lower_cache()
    assert not LOWER_CACHE_STATS["tri_cumsums"]


def test_memo_hit_keeps_the_count(scan_program):
    lower(scan_program.compiled)     # assigns fused_group ids (hash settles)
    clear_lower_cache()
    first = lower(scan_program.compiled)
    again = lower(scan_program.compiled)
    assert LOWER_CACHE_STATS["hits"] == 1
    assert again.fn is first.fn and again.tri_cumsums == first.tri_cumsums == 2


def _load_block(config):
    path = ROOT / "bench" / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(config.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.block


@pytest.mark.parametrize("config", ["gpt2-medium-block", "resnet18-224"])
def test_benchmark_blocks_without_cumsum_count_none(config):
    """The benchmark's blocks with no cumsum task lower exactly as before:
    no triangular step."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    program = codo.compile(_load_block(config),
                           *[tuple(s) for s in cfg["inputs"]], cache=None)
    lowered = lower(program.compiled, memo=False)
    assert lowered.tri_cumsums == 0
    assert LOWER_CACHE_STATS["tri_cumsums"][lowered.name] == 0

