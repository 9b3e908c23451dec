"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: ``jax.experimental.topologies`` describes a v5e and the
TPU compiler that ships with jax compiles for it, refusing what the chip
would refuse (block tiling, VMEM) — the failures interpret mode never
shows.  Nothing runs, so these tests say nothing about numbers or times.

Widths are the main path's: the ``gpt2_block`` at GPT-2-medium width
(S = 1024, D = 1024) through the routing factories, the streamfuse
softmax·matmul and gradient-epilogue kernels at 1024², the RG-LRU scan at
recurrentgemma-9b's width (one sequence of 1024 x 4096), the SSD chunk
scan at mamba2-780m's (48 heads of 64 x 128 state, 4 chunks of 256
tokens) and the conv chain at a resnet18 stage-1 layer; besides the
kernels, granite's cumulative sums as triangular matmuls.

The topology is described in a fixture (never at import), and jax's
persistent compilation cache is off around the compiles: an entry written
for a described chip cannot be read back without one.
"""

import functools

import pytest

S, D = 1024, 1024
KERNEL = 'custom_call_target="tpu_custom_call"'


def _kernel_names(text: str) -> set[str]:
    """The names of the Mosaic kernel calls in compiled HLO text, without
    XLA's ``.<n>`` suffix: the names a device trace shows."""
    return {line.split("=")[0].strip().removeprefix("ROOT ").lstrip("%")
            .rsplit(".", 1)[0]
            for line in text.splitlines() if KERNEL in line}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the routing factories choose their compiled (``pallas``)
    mode, as they do on a TPU backend."""
    import jax
    from repro.kernels.common import kernel_mode
    monkeypatch.delenv("CODO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_mode() == "pallas"


def _compile(fn, shapes, sharding) -> str:
    """Compiled HLO text of ``fn`` over f32 operands of ``shapes`` (a
    list of shapes, or a dict of them for an env-taking step)."""
    import jax
    import jax.numpy as jnp

    def spec(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)

    if isinstance(shapes, dict):
        args = ({k: spec(s) for k, s in shapes.items()},)
    else:
        args = [spec(s) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _routed_steps(fn, *specs):
    """Trace and compile ``fn``, then build every structurally matched
    chain's step through its pattern's factory: ``{pattern: (step,
    operand shapes)}``, ``step`` None where the factory declined."""
    import repro.api as codo
    from repro.core.lowering import fusion_groups
    from repro.core.routing import ensure_kernel_patterns, match_group
    ensure_kernel_patterns()
    c = codo.compile(fn, *specs, cache=None).compiled
    graph, impl = c.graph, c.buffer_plan.impl
    out = {}
    for g in fusion_groups(graph, impl):
        for pat, tasks in match_group(graph, g.tasks, impl):
            made = {t.writes[0].buffer for t in tasks}
            ext = {a.buffer for t in tasks for a in t.reads} - made
            shapes = {b: tuple(graph.buffers[b].shape) for b in ext}
            out[pat.name] = (pat.factory(graph, g, tasks), shapes)
    return out


def test_gpt2_block_routes_compile(one_chip, on_tpu):
    """At GPT-2-medium width flashattn takes the attention chain (8 MiB of
    resident K/V fits the 12 MiB budget) and the FFN mmchain declines
    (32 MiB of weights; the compiler would refuse it for VMEM)."""
    from repro.models.dataflow_models import gpt2_block_fn
    steps = _routed_steps(gpt2_block_fn, (S, D))
    assert set(steps) == {"flashattn.mha", "streamfuse.mmchain"}
    ffn_step, _ = steps["streamfuse.mmchain"]
    assert ffn_step is None
    step, shapes = steps["flashattn.mha"]
    assert step is not None and len(shapes) == 3
    assert _kernel_names(_compile(step, shapes, one_chip)) == {"flashattn_mha"}


def test_mmchain_route_compiles_where_the_guard_admits(one_chip, on_tpu):
    from repro.core import frontend as F

    def mlp(x):
        return F.fc(F.gelu(F.fc(x, D)), D)

    step, shapes = _routed_steps(mlp, (S, D))["streamfuse.mmchain"]
    assert step is not None
    assert _kernel_names(_compile(step, shapes, one_chip)) == {
        "streamfuse_mmchain"}


def _relu_grad(h, r):
    return h * (r > 0)


@pytest.mark.parametrize("name,shapes", [
    ("softmaxmm", [(S, S), (S, D)]),
    ("mmgrad", [(S, D), (D, D), (S, D)]),
    ("rglru.scan", [(1, S, 4096), (1, S, 4096)]),
    ("ssd.scan", [(4, 48, 64, 128), (4, 48, 1, 1)]),
    ("streamfuse.conv", [(1, 64, 56, 56), (64, 64, 3, 3)]),
])
def test_kernel_compiles(one_chip, name, shapes):
    from repro.kernels.rglru.rglru import rglru_scan
    from repro.kernels.ssd.ssd import ssd_chunk_scan
    from repro.kernels.streamfuse.chain import (fused_matmul_grad,
                                                fused_softmax_matmul)
    from repro.kernels.streamfuse.streamfuse import fused_pad_conv_relu
    fn = {"softmaxmm": fused_softmax_matmul,
          "mmgrad": functools.partial(fused_matmul_grad, ew=_relu_grad),
          "rglru.scan": rglru_scan,
          "ssd.scan": ssd_chunk_scan,
          "streamfuse.conv": fused_pad_conv_relu}[name]
    kernel = {"softmaxmm": "streamfuse_softmaxmm",
              "mmgrad": "streamfuse_mmgrad"}.get(name, name.replace(".", "_"))
    assert _kernel_names(_compile(fn, shapes, one_chip)) == {kernel}



def _granite():
    """granite-4.0-h-micro's period (the benchmark's configuration) and its
    sizes at the published widths."""
    import importlib.util
    import json
    from pathlib import Path
    configs = Path(__file__).resolve().parents[1] / "bench" / "configs"
    name = "granite-4.0-h-micro-period"
    spec = importlib.util.spec_from_file_location("granite_period",
                                                  configs / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod._sizes(json.loads((configs / f"{name}.json").read_text()))


def _granite_route(fn, shape, pattern):
    """The first ``pattern`` chain of ``fn`` traced at ``shape``: (step,
    shapes of the buffers the step reads)."""
    import repro.api as codo
    from repro.core.lowering import fusion_groups
    from repro.core.routing import ensure_kernel_patterns, match_group
    ensure_kernel_patterns()
    c = codo.compile(fn, shape, cache=None).compiled
    graph, impl = c.graph, c.buffer_plan.impl
    for g in fusion_groups(graph, impl):
        for pat, tasks in match_group(graph, g.tasks, impl):
            if pat.name == pattern:
                return graph, pat.factory(graph, g, tasks), tasks
    raise AssertionError(f"no {pattern} chain")


def test_granite_attention_route_compiles(one_chip, on_tpu):
    """The causal GQA chain at granite's widths (32 query heads over 8 K/V
    heads of 64, S = 4096): its K/V are 16 MiB whole, a grid step holds
    128-row blocks; the step reads the 8 K/V heads, not repeated copies."""
    from repro.kernels.flashattn.patterns import kv_sources
    mod, z = _granite()
    graph, step, tasks = _granite_route(
        functools.partial(mod._attention, z), (4096, 2048), "flashattn.mha")
    assert step is not None
    kt, v, group = kv_sources(graph, tasks)
    assert group == 4 and graph.buffers[kt].shape == (8, 64, 4096)
    q = tasks[0].spec.ins[0]
    shapes = {b: tuple(graph.buffers[b].shape) for b in (q, kt, v)}
    assert _kernel_names(_compile(step, shapes, one_chip)) == {"flashattn_mha"}


def test_granite_ssd_route_compiles(one_chip, on_tpu):
    """The SSD chunk scan at granite's widths: 16 chunks of 64 heads of
    64 x 128 state; 32 MiB whole, 2 MiB a chunk."""
    import repro.api as codo
    from repro.core import frontend as F
    from repro.kernels.ssd.patterns import factory
    _mod, z = _granite()
    shapes = [(16, z["H"], z["P"], z["N"]), (16, z["H"], 1, 1)]
    graph = codo.compile(F.ssd_scan, *shapes, cache=None).compiled.graph
    (task,) = [t for t in graph.tasks if t.spec.kind == "ssd_scan"]
    step = factory(graph, None, [task])
    assert step is not None
    env = dict(zip(task.spec.ins, shapes))
    assert _kernel_names(_compile(step, env, one_chip)) == {"ssd_scan"}


@pytest.mark.parametrize("shape,axis", [
    ((16, 64, 256, 256), 2),          # the segment sums of a Mamba-2 layer
    ((16, 256, 64), 1),               # the chunk cumsum of its steps
])
def test_granite_cumsums_compile_as_contractions(one_chip, shape, axis):
    """granite's two cumulative sums a Mamba-2 layer, at published widths,
    lowered as triangular matmuls: contractions at ``HIGHEST``, and no
    reduce-window over the scanned axis (one may remain over the block
    totals, a block-count long)."""
    import math
    import re
    from repro.core.lowering import TRI_CUMSUM_BLOCK, tri_cumsum
    text = _compile(lambda x: tri_cumsum(x, axis), [shape], one_chip)
    assert "operand_precision={highest,highest}" in text
    n = shape[axis]
    nb = -(-n // min(n, TRI_CUMSUM_BLOCK))
    for dims in re.findall(r"= f32\[([\d,]+)\]\S* reduce-window\(", text):
        assert math.prod(map(int, dims.split(","))) <= (
            math.prod(shape) // n * nb)
