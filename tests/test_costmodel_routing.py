"""Cost-gated routing: the gate is the only place a kernel is decided.

Covers the CPU softmaxmm fallback the committed constants encode, the
``forced > predicted`` decision precedence with its env overrides, the
hardware the gate prices on a TPU, and the routes the gate keeps in each
benchmark cell when pricing and building as on a v5e.  Kernel numerics
and the matcher itself live in ``tests/test_routing.py``.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

from repro.core import CodoOptions, codo_opt
from repro.core.costmodel import routing_backend, routing_params
from repro.core.lowering import lower
from repro.core.routing import ROUTED_DECISIONS, XLA_FUSED
from repro.kernels import register_all
from repro.models import dataflow_models as dm

register_all()

REPO = Path(__file__).resolve().parents[1]


def _compile(graph, budget=64):
    return codo_opt(graph, CodoOptions.preset("opt5", budget_units=budget),
                    cache=None)


# --------------------------------------------------------------------------
# The predictor vs the recorded bench
# --------------------------------------------------------------------------


def test_softmaxmm_tail_stays_generic_on_cpu(monkeypatch):
    """The softmaxmm kernel's CPU constant is 0.97x, so the gate must
    route the attention tail to generic XLA there — at any size.  In
    gpt2_block the full-chain ``flashattn.mha`` now supersedes this tail
    (see test_flashattn_supersedes_softmaxmm_in_gpt2), so the bare tail
    is exercised on a graph whose chain *starts* at the softmax."""
    monkeypatch.delenv("CODO_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("CODO_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("CODO_BACKEND", "cpu")
    # CPU has no spill/overlap terms, so the win condition reduces to
    # eff * (1 + slack) > 1; softmaxmm's committed 0.97 keeps it losing
    # regardless of chain size.
    p = routing_params("cpu")
    assert p.eff("streamfuse.softmaxmm") * (1.0 + p.slack) < 1.0
    from repro.core.frontend import GB
    b = GB("sm_tail")
    s = b.input("s", (64, 64))
    v = b.input("v", (64, 64))
    b.mark_output(b.matmul(b.softmax(s), v))
    c = _compile(b.g)
    low = lower(c, jit=False)
    assert all(r.kernel != "streamfuse.softmaxmm"
               for g in low.groups for r in g.routes)
    rej = [r for g in low.groups for r in g.rejected
           if r.kernel == "streamfuse.softmaxmm"]
    assert rej, "the softmaxmm chain must still structurally match"
    assert all(r.decision == "predicted-loss" for r in rej)
    # ...and the verdict rides on the diagnostics with both estimates
    entries = c.diagnostics.group_kernels.values()
    assert any(any(rr["kernel"] == "streamfuse.softmaxmm"
                   and rr["decision"] == "predicted-loss"
                   for rr in e["rejected"]) for e in entries)


# --------------------------------------------------------------------------
# Decision precedence + env overrides
# --------------------------------------------------------------------------


def test_force_and_disable_override_precedence(monkeypatch):
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")
    c = _compile(dm.feed_forward(16, 32))       # below the win threshold
    low = lower(c, jit=False)
    routed = [r for g in low.groups for r in g.routes]
    assert routed, "CODO_FORCE_PALLAS must route gate-rejected chains"
    assert all(r.decision == "forced" for r in routed)
    assert all(r.decision in ROUTED_DECISIONS for r in routed)

    monkeypatch.setenv("CODO_DISABLE_PALLAS", "1")   # disable beats force
    low2 = lower(c, jit=False)
    assert all(not g.routes for g in low2.groups)
    assert all(g.kernel == XLA_FUSED for g in low2.groups)
    assert any(g.decision == "disabled" for g in low2.groups)


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_device_hw_chosen_by_device_kind(monkeypatch):
    """On a TPU the gate prices the chip jax reports; a kind the cost
    model has no numbers for raises instead of borrowing v5e's."""
    import jax
    from repro.core.costmodel import V5E, HwParams, device_hw
    other = HwParams(name="modeled")
    assert device_hw(other) is other                 # CPU: modeled target
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    assert device_hw(other) is V5E
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        device_hw(other)


def test_routing_backend_does_not_swallow_import_errors(monkeypatch):
    monkeypatch.delenv("CODO_BACKEND", raising=False)
    monkeypatch.setitem(sys.modules, "jax", None)
    with pytest.raises(ImportError):
        routing_backend()


# --------------------------------------------------------------------------
# The chip's routing, per benchmark cell
# --------------------------------------------------------------------------


def _bench_config(name: str):
    """A benchmark configuration's builder module and its JSON spec, read
    from ``bench/configs`` as the benchmark harness reads them."""
    path = REPO / "bench" / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "cell_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, json.loads(path.with_suffix(".json").read_text())


@pytest.fixture
def on_v5e(monkeypatch):
    """Price and build as on a TPU v5e: the factories choose their
    compiled (``pallas``) mode, so their VMEM guards apply, and the gate
    prices the chip jax reports."""
    import jax
    from repro.kernels.common import kernel_mode
    for k in ("CODO_PALLAS_INTERPRET", "CODO_FORCE_PALLAS",
              "CODO_DISABLE_PALLAS", "CODO_BACKEND"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev("TPU v5 lite")])
    assert kernel_mode() == "pallas" and routing_backend() == "tpu"


@pytest.mark.parametrize("cell,want", [
    ("gpt2m-fwd", 1), ("resnet18-fwd", 5), ("gpt2m-train", 1),
    ("granite-h-prefill-4k", 10)])
def test_chip_routes_per_benchmark_cell(on_v5e, cell, want):
    """The routes ``lower()`` keeps in each cell's program at the cell's
    own shapes, counted as the harness counts ``setup.routes``: every
    route of the forward program, or of all three train-step phases."""
    import repro.api as codo
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    mod, spec = _bench_config(w["config"])
    if w["traffic"] == "train-closed":
        step = codo.compile(mod.loss, *map(tuple, spec["train_inputs"]),
                            grad=True, opt=spec["optimizer"], cache=None)
        programs = [step.forward, step.backward, step.update]
    else:
        programs = [codo.compile(mod.block, *map(tuple, spec["inputs"]),
                                 cache=None)]
    routes = [r for p in programs for g in p.lower(jit=True).groups
              for r in g.routes]
    assert len(routes) == want, [r.kernel for r in routes]
    assert all(r.decision == "predicted-win" for r in routes)
