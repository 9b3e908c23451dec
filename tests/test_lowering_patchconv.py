"""Lane-starved convolutions lowered as one patch matmul.

An ungrouped ``conv2d`` task with few input channels and a patch at least
a lane deep runs as ``patch_conv2d`` (the input's rows and columns put in
phase-major order, the taps cut from it as contiguous windows, one
``dot_general`` at ``HIGHEST``) in place of ``lax.conv_general_dilated``;
everything else keeps the registry's conv.  Sharded programs take the
same rule: a subprocess on 4 host devices checks them.
"""

import importlib.util
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.api as codo
from repro.core import frontend as F
from repro.core import lowering
from repro.core.lowering import (LOWER_CACHE_STATS, clear_lower_cache, lower,
                                 patch_conv2d, patch_conv_rule,
                                 takes_patch_conv, verify_lowering)
from repro.models.dataflow_models import gpt2_block_fn

ROOT = Path(__file__).resolve().parents[1]
STRATEGIES = ("replicate", "dp", "tp", "auto")


def _lax_conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("x_shape,w_shape,stride,pad", [
    ((1, 3, 224, 224), (64, 3, 7, 7), 2, 3),      # ResNet-18's stem
    ((1, 3, 16, 16), (8, 3, 7, 7), 2, 3),
    ((2, 4, 9, 11), (5, 4, 3, 3), 1, 1),
    ((1, 8, 12, 12), (6, 8, 5, 5), 3, 2),
    ((1, 16, 10, 10), (8, 16, 3, 3), 2, 0),
    ((3, 2, 8, 7), (4, 2, 1, 1), 1, 0),
    ((1, 64, 14, 14), (32, 64, 3, 3), 1, 1),
])
def test_patch_conv_matches_lax_conv(x_shape, w_shape, stride, pad):
    rng = np.random.default_rng(sum(x_shape) + stride)
    x = rng.standard_normal(x_shape).astype(np.float32)
    w = rng.standard_normal(w_shape).astype(np.float32)
    x = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    want = np.asarray(_lax_conv(x, w, stride))
    got = np.asarray(patch_conv2d(x, w, stride))
    assert got.shape == want.shape
    # Only the order of the f32 sum over (c, kh, kw) differs.
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("groups,c_in,kh,kw,patched", [
    (1, 3, 7, 7, True),           # the stem: 147 deep
    (1, 16, 3, 3, True),          # 144 deep
    (1, 64, 3, 3, True),          # half the lanes
    (3, 3, 7, 7, False),          # depthwise
    (2, 8, 5, 5, False),          # grouped
    (1, 64, 1, 1, False),         # 1x1: 64 deep
    (1, 128, 3, 3, False),        # lanes full already
    (1, 128, 1, 1, False),
    (1, 512, 3, 3, False),
    (1, 3, 3, 3, False),          # 27 deep: under a lane
    (1, 8, 3, 3, False),          # 72 deep
])
def test_patch_conv_rule(groups, c_in, kh, kw, patched):
    assert patch_conv_rule(groups, c_in, kh, kw) is patched


def _conv_net(x):
    h = F.conv(x, 16, 7, stride=2, pad=3)              # 3 -> 16, 147 deep
    h = F.conv(h, 16, 3, depthwise=True)               # depthwise
    h = F.conv(h, 8, 1, pad=0, relu=False)             # 1x1
    return F.conv(h, 4, 3, relu=False)                 # 8 -> 4, 72 deep


@pytest.mark.parametrize("kind,patched", [
    ("stem", True), ("depthwise", False), ("1x1", False), ("shallow", False),
])
def test_gate_on_graph_tasks(kind, patched):
    """Which of a small net's conv tasks take the patch path, and the
    scope their step is emitted under."""
    program = codo.compile(_conv_net, (1, 3, 20, 20), cache=None)
    graph = program.graph
    convs = [t for t in graph.toposort() if t.op == "conv"]
    assert len(convs) == 4
    t = convs[["stem", "depthwise", "1x1", "shallow"].index(kind)]
    assert takes_patch_conv(graph, t) is patched
    groups = lowering.fusion_groups(graph, program.compiled.buffer_plan.impl)
    scopes = {n: scope for n, scope, _fn in
              lowering._build_steps(graph, groups, False)}
    assert scopes[t.name].endswith(".patchconv" if patched else ".xla")


def test_lowered_net_matches_oracle_and_counts():
    clear_lower_cache()
    program = codo.compile(_conv_net, (1, 3, 20, 20), cache=None)
    lowered = lower(program.compiled, jit=False,
                    use_registered_kernels=False)
    assert lowered.patch_convs == 1
    assert LOWER_CACHE_STATS["patch_convs"][lowered.name] == 1
    assert "1 patch-matmul convs" in lowered.summary()
    rng = np.random.default_rng(0)
    env = program.make_env(rng.standard_normal((1, 3, 20, 20))
                           .astype(np.float32))
    verify_lowering(program.source, program.compiled, env)
    clear_lower_cache()
    assert not LOWER_CACHE_STATS["patch_convs"]


def test_memo_hit_keeps_the_count():
    program = codo.compile(_conv_net, (1, 3, 20, 20), cache=None)
    lower(program.compiled)      # assigns fused_group ids (hash settles)
    clear_lower_cache()
    first = lower(program.compiled)
    again = lower(program.compiled)
    assert LOWER_CACHE_STATS["hits"] == 1
    assert again.fn is first.fn and again.patch_convs == first.patch_convs == 1


def _load_block(config):
    path = ROOT / "bench" / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(config.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.block


def test_resnet18_224_counter_follows_the_rule():
    """The lowered ``resnet18-224`` block (no trace, no compile) runs as
    patch matmuls exactly the XLA-lowered conv tasks the rule admits: the
    stem and the three 3x3 convolutions of 64 input channels that no
    routed chain takes."""
    program = codo.compile(_load_block("resnet18-224"), (1, 3, 224, 224),
                           cache=None)
    lowered = lower(program.compiled, memo=False)
    graph = program.graph
    routed = {n for g in lowered.groups for r in g.routes for n in r.tasks}
    want = 0
    for t in graph.tasks:
        if t.spec is None or t.spec.kind != "conv2d" or t.name in routed:
            continue
        _o, c_in, kh, kw = graph.buffers[t.spec.ins[1]].shape
        want += patch_conv_rule(int(t.spec.attrs.get("groups", 1)),
                                c_in, kh, kw)
    assert want == 4
    assert lowered.patch_convs == want
    assert LOWER_CACHE_STATS["patch_convs"][lowered.name] == want


def test_gpt2_block_counts_no_patch_conv():
    program = codo.compile(gpt2_block_fn, (64, 128), cache=None)
    lowered = lower(program.compiled, memo=False)
    assert lowered.patch_convs == 0
    assert LOWER_CACHE_STATS["patch_convs"][lowered.name] == 0


SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path.insert(0, {src!r})
    import numpy as np

    import repro.api as codo
    from repro.core import frontend as F
    from repro.core.lowering import lower, verify_lowering, verify_sharding
    from repro.distributed.partition import partition
    from repro.launch.mesh import make_debug_mesh

    def net(x):
        h = F.conv(x, 16, 7, stride=2, pad=3)
        h = F.conv(h, 16, 3, depthwise=True)
        h = F.conv(h, 8, 1, pad=0, relu=False)
        return F.conv(h, 4, 3, relu=False)

    prog = codo.compile(net, (4, 3, 20, 20), cache=None)
    env = prog.make_env(np.random.default_rng(0).standard_normal(
        (4, 3, 20, 20)).astype(np.float32))
    mesh = make_debug_mesh((2, 2), ("data", "model"))
    out = {{}}
    for strat in {strategies!r}:
        plan = partition(prog.compiled, mesh, strat)
        verify_sharding(prog.compiled, plan, dict(env))
        verify_lowering(prog.source, prog.compiled, dict(env),
                        sharding=plan)
        out[strat] = lower(prog.compiled, jit=False,
                           sharding=plan).patch_convs
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_patch_convs(tmp_path_factory):
    script = SHARDED.format(src=str(ROOT / "src"), strategies=STRATEGIES)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          cwd=tmp_path_factory.mktemp("sharded_conv"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_program_takes_the_rule(sharded_patch_convs, strategy):
    """A 2x2 mesh of host devices runs the small net's stem as a patch
    matmul, and its outputs match the single-device lowering and the
    oracle (checked in the subprocess)."""
    assert sharded_patch_convs[strategy] == 1
