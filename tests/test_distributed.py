"""Sharding rules, HLO analysis, multi-device paths (subprocess: the
device count must be fixed before jax initializes)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import sanitize_spec
from repro.launch import hlo_analysis as ha

SRC = str(Path(__file__).resolve().parents[1] / "src")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_sanitize_spec_drops_undivisible_when_lenient():
    mesh = FakeMesh({"data": 16, "model": 16})
    s = lambda *a, **k: sanitize_spec(*a, strict=False, **k)  # noqa: E731
    assert s(P("data"), (1,), mesh) == P(None)
    assert s(P("data", "model"), (32, 7), mesh) == P("data", None)
    assert s(P(("pod", "data"),), (32,),
             FakeMesh({"pod": 2, "data": 16})) == P(("pod", "data"))
    assert s(P(("pod", "data"),), (2,),
             FakeMesh({"pod": 2, "data": 16})) == P("pod")


def test_sanitize_spec_strict_rejects_undivisible():
    from repro.distributed.sharding import ShardingSpecError
    mesh = FakeMesh({"data": 16, "model": 16})
    with pytest.raises(ShardingSpecError, match="does not divide"):
        sanitize_spec(P("data", "model"), (32, 7), mesh)
    with pytest.raises(ShardingSpecError, match="only has axes"):
        sanitize_spec(P("pod"), (32,), mesh)
    # a clean spec passes through untouched
    assert sanitize_spec(P("data", "model"), (32, 32), mesh) \
        == P("data", "model")


def test_param_specs_cover_all_archs():
    mesh = FakeMesh({"data": 16, "model": 16})
    from repro.distributed.sharding import param_specs
    from repro.models import transformer as tf
    for arch in ("gemma-7b", "mixtral-8x22b", "mamba2-780m",
                 "recurrentgemma-9b", "whisper-large-v3"):
        cfg = get_config(arch)
        shapes = tf.param_shapes(cfg)
        specs = param_specs(shapes, mesh, cfg)
        import math
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        # every *large* tensor (>= 1M elements) must be sharded on >= 1 axis
        for leaf, spec in zip(flat_shapes, flat_specs):
            if math.prod(leaf.shape) >= 1_000_000:
                assert any(e is not None for e in spec), \
                    f"{arch}: unsharded large leaf {leaf.shape} {spec}"


# --------------------------------------------------------------------------
# HLO walker
# --------------------------------------------------------------------------


def test_hlo_walker_counts_scan_trips():
    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return jnp.sum(h)

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    cost = ha.analyze(jax.jit(f).lower(w, x).compile().as_text())
    expect = 8 * 2 * 32 * 128 * 128
    assert abs(cost.flops - expect) / expect < 0.01


def test_hlo_walker_nested_and_grad():
    def f(w, x):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=4)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=8)
        return jnp.sum(h)

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    fwd = ha.analyze(jax.jit(f).lower(w, x).compile().as_text())
    assert abs(fwd.flops - 32 * 2 * 16 * 64 * 64) / fwd.flops < 0.01
    bwd = ha.analyze(jax.jit(jax.grad(f)).lower(w, x).compile().as_text())
    assert bwd.flops >= 2.5 * fwd.flops          # fwd + 2 bwd matmuls


# --------------------------------------------------------------------------
# multi-device (subprocess with 8 host devices)
# --------------------------------------------------------------------------

MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import sys
    sys.path.insert(0, {src!r})

    out = {{}}

    # 1) jitted sharded train step on a 4x2 debug mesh
    from repro.launch.mesh import make_debug_mesh
    from repro.configs import get_config
    from repro.models import transformer as tf, make_batch
    from repro.training.train_loop import jit_train_step
    from repro.training.optimizer import adamw_init, OptConfig

    mesh = make_debug_mesh((4, 2), ("data", "model"))
    cfg = get_config("gpt2-medium").smoke()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = make_batch(cfg, batch=4, seq=64, kind="train")
    step = jit_train_step(cfg, mesh, params, batch,
                          OptConfig(lr=1e-3, warmup_steps=1, total_steps=4))
    with jax.set_mesh(mesh):
        for _ in range(3):
            params, opt, metrics = step(params, opt, batch)
    out["train_loss"] = float(metrics["loss"])

    # 2) pipeline executor vs serial reference on a 4-stage mesh
    from repro.core.pipeline import pipeline_fn, reference_serial, PipelineSchedule
    pmesh = make_debug_mesh((4,), ("stage",))
    D = 16
    def stage(p, x):
        return jnp.tanh(x @ p["w"])
    fns = [stage] * 4
    key = jax.random.PRNGKey(1)
    pstack = {{"w": jax.random.normal(key, (4, D, D)) * 0.5}}
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 4, D))  # (nmb, mb, D)
    y_pipe = pipeline_fn(fns, pmesh)(pstack, x)
    y_ref = reference_serial(fns, pstack, x)
    out["pipe_err"] = float(jnp.abs(y_pipe - y_ref).max())
    out["bubble"] = PipelineSchedule(4, 8).bubble_fraction

    # 3) compressed all-reduce under shard_map matches plain mean-free sum
    from repro.distributed import compression
    cmesh = make_debug_mesh((8,), ("data",))
    g_global = jax.random.normal(jax.random.PRNGKey(3), (8, 64)) * 1e-2
    def worker(g):
        grads = {{"g": g[0]}}
        st = {{}}
        red, st = compression.compressed_allreduce(grads, st, ("data",))
        return red["g"][None]
    red = jax.jit(jax.shard_map(worker, mesh=cmesh, in_specs=P("data"),
                                out_specs=P("data"),
                                check_vma=False))(g_global)
    want = jnp.sum(g_global, axis=0)
    err = jnp.abs(red[0] - want).max() / (jnp.abs(want).max() + 1e-9)
    out["allreduce_rel_err"] = float(err)

    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def multidev_results():
    script = MULTIDEV.format(src=SRC)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_sharded_train_step(multidev_results):
    assert np.isfinite(multidev_results["train_loss"])


def test_pipeline_executor_matches_serial(multidev_results):
    assert multidev_results["pipe_err"] < 1e-5
    assert 0 < multidev_results["bubble"] < 0.5


def test_compressed_allreduce(multidev_results):
    assert multidev_results["allreduce_rel_err"] < 0.02
