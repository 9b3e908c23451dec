"""granite-4.0-h-micro's period (bench/configs/granite-4.0-h-micro-period)
at a small size on the CPU: ``codo.compile`` of its block against its
plain reference, with the routed kernels as the gate leaves them and with
both forced into their Pallas bodies (interpret mode); the reference's
chunked SSD against the sequential recurrence; the flash pattern's causal
GQA claim; and the kernels' per-grid-step VMEM guards.

Small size: d 64, 4 query / 2 K/V heads of 16, Mamba-2 heads 4 x 16,
state 16, chunk 32, MLP 128, S 128, the published ten-layer pattern."""

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import codo
from repro.core import frontend as F
from repro.core.lowering import fusion_groups, lower
from repro.core.routing import ensure_kernel_patterns, match_group

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
NAME = "granite-4.0-h-micro-period"
SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
         "mamba_chunk_size": 32, "shared_intermediate_size": 128}
S = 128


def _highest():
    from types import SimpleNamespace
    return SimpleNamespace(mm=functools.partial(jnp.matmul,
                                                precision="highest"))


@pytest.fixture(scope="module")
def model():
    spec = importlib.util.spec_from_file_location("granite_period",
                                                  CONFIGS / f"{NAME}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return {**json.loads((CONFIGS / f"{NAME}.json").read_text()), **SMALL}


@pytest.fixture(scope="module")
def compiled(model, cfg):
    """The period compiled once, with seeded weights and input."""
    program = codo.compile(functools.partial(model.period, cfg), (S, 64),
                           cache=None)
    rng = np.random.default_rng(11)
    ws = [(rng.standard_normal(b.shape)
           / np.sqrt(b.shape[0] if len(b.shape) == 2 else 1)).astype(
               np.float32) for b in program.source.weights()]
    program.bind(**{b.name: w for b, w in zip(program.source.weights(), ws)})
    x = rng.standard_normal((S, 64)).astype(np.float32)
    want = np.asarray(jax.jit(lambda w, x: model.reference(
        cfg, _highest(), w, x))(ws, x))
    return program, ws, x, want


def _rel(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_period_layers_and_regions(model, cfg, compiled):
    program = compiled[0]
    assert model.layer_kinds(cfg) == ["mamba"] * 5 + ["attention"] + \
        ["mamba"] * 4
    kinds = [t.spec.kind for t in program.source.tasks]
    # Two masks a Mamba-2 layer (its stable segment sums), one for attention.
    assert kinds.count("ssd_scan") == 9 and kinds.count("causal_mask") == 19
    assert kinds.count("repeat_heads") == 2
    from repro.core.graph import task_region
    assert {task_region(t) for t in program.source.tasks} == {
        "mamba", "attn", "mlp"}


def test_period_matches_reference(compiled):
    program, _ws, x, want = compiled
    # f32 sums reassociated: the program's SSD carries chunk states through
    # a sequential scan where the reference multiplies by a matrix of
    # chunk decays, and XLA fuses each side differently; ten layers of it
    # read 6e-7 of the output's largest magnitude.
    assert _rel(program(x), want) < 1e-5


def test_period_with_kernels_matches_reference(compiled, monkeypatch):
    program, _ws, x, want = compiled
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    low = lower(program.compiled, jit=True)
    kernels = sorted(r.kernel for g in low.groups for r in g.routes)
    assert kernels == ["flashattn.mha"] + ["ssd.scan"] * 9
    env = program.make_env(x)
    got = low(env)[program.output_names[0]]
    # The same reassociation, plus the kernels' online softmax and their
    # own order of the state updates: 7e-7 observed.
    assert _rel(got, want) < 1e-5


def test_chunked_reference_ssd_matches_the_recurrence(model):
    rng = np.random.default_rng(5)
    s, H, P, N, L = 64, 3, 4, 5, 16
    X = rng.standard_normal((s, H, P)).astype(np.float32)
    A = -rng.uniform(0.01, 1.0, (s, H)).astype(np.float32)
    B, C = (rng.standard_normal((s, N)).astype(np.float32) for _ in "BC")
    got = model.ssd_minimal(_highest(), jnp.asarray(X), jnp.asarray(A),
                            jnp.asarray(B), jnp.asarray(C), L)

    def step(h, inp):
        x, a, b, c = inp
        h = jnp.exp(a)[:, None, None] * h + x[:, :, None] * b[None, None]
        return h, jnp.einsum("hpn,n->hp", h, c, precision="highest")

    _, want = jax.lax.scan(step, jnp.zeros((H, P, N)), (X, A, B, C))
    # exp of cumulative-sum differences against a running product of
    # exps: f32 rounding of the sums, ~1e-6 of the largest output.
    assert _rel(got, np.asarray(want)) < 1e-5


def _attention_graph(hq=4, hkv=2, hd=16, s=64, d=64):
    def attn(x):
        q = F.transpose(F.reshape(F.fc(x, hq * hd), (s, hq, hd)), (1, 0, 2))
        kt = F.transpose(F.reshape(F.fc(x, hkv * hd), (s, hkv, hd)),
                         (1, 2, 0))
        v = F.transpose(F.reshape(F.fc(x, hkv * hd), (s, hkv, hd)),
                        (1, 0, 2))
        sc = F.scale(F.matmul(q, F.repeat_heads(kt, hq // hkv)), 0.25)
        return F.matmul(F.softmax(F.causal_mask(sc)),
                        F.repeat_heads(v, hq // hkv))
    return codo.compile(attn, (s, d), cache=None)


def test_flash_claims_the_causal_gqa_chain(monkeypatch):
    from repro.kernels.flashattn import flashattn
    ensure_kernel_patterns()
    program = _attention_graph()
    graph, impl = program.compiled.graph, program.compiled.buffer_plan.impl
    matches = [(p, t) for g in fusion_groups(graph, impl)
               for p, t in match_group(graph, g.tasks, impl)
               if p.name == "flashattn.mha"]
    assert len(matches) == 1
    pat, tasks = matches[0]
    assert [t.spec.kind for t in tasks] == [
        "matmul", "scale", "causal_mask", "softmax", "matmul"]
    seen = []
    real = flashattn.flash_attention

    def spy(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["causal"]))
        return real(q, k, v, **kw)

    monkeypatch.setattr(flashattn, "flash_attention", spy)
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    step = pat.factory(graph, None, tasks)
    env = {b: np.random.default_rng(1).standard_normal(
        graph.buffers[b].shape).astype(np.float32) for b in graph.buffers}
    out = np.asarray(step(env)[tasks[-1].spec.outs[0]])
    # The kernel reads the 2 K/V heads, not the 4 repeated copies.
    assert seen == [((1, 4, 64, 16), (1, 2, 64, 16), (1, 2, 64, 16), True)]
    kt_src = graph.producers(tasks[0].spec.ins[1])[0].spec.ins[0]
    v_src = graph.producers(tasks[-1].spec.ins[1])[0].spec.ins[0]
    q = env[tasks[0].spec.ins[0]]
    k = np.repeat(np.swapaxes(env[kt_src], 1, 2), 2, axis=0)
    v = np.repeat(env[v_src], 2, axis=0)
    sc = 0.25 * np.einsum("hqd,hkd->hqk", q, k)
    sc = np.where(np.tri(64, dtype=bool), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)
    # Online softmax against a two-pass one, f32: a few ulps.
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.fixture
def on_tpu(monkeypatch):
    """The factories' compiled (``pallas``) mode, as on a TPU backend."""
    monkeypatch.delenv("CODO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _ssd_graph(bh):
    return codo.compile(F.ssd_scan, (16, bh, 64, 128), (16, bh, 1, 1),
                        cache=None).compiled.graph


@pytest.mark.parametrize("bh,admitted", [(64, True), (128, False)])
def test_ssd_guard_counts_one_grid_step(bh, admitted, on_tpu):
    """A step holds one chunk: (64, 64, 128) states in and out, double-
    buffered, and the running state, 10.5 MiB with the decay blocks; the
    whole (16, 64, 64, 128) operand is 32 MiB.  At 128 heads a step holds
    21 MiB, over the 12 MiB budget."""
    from repro.kernels.common import vmem_ok
    from repro.kernels.ssd.patterns import factory, step_vmem_ok
    assert not vmem_ok((16, bh, 64, 128))
    assert step_vmem_ok((16, bh, 64, 128)) is admitted
    graph = _ssd_graph(bh)
    (task,) = [t for t in graph.tasks if t.spec.kind == "ssd_scan"]
    assert (factory(graph, None, [task]) is not None) is admitted


def test_flash_guard_counts_one_grid_step():
    """granite's (8, 4096, 64) K/V are 16 MiB whole; a step holds 128-row
    blocks.  GPT-2-medium's head of 1024 is admitted as before."""
    from repro.kernels.common import vmem_ok
    from repro.kernels.flashattn.patterns import step_vmem_ok
    assert not vmem_ok((8, 64, 4096), (8, 4096, 64))
    assert step_vmem_ok(64, 128, 128) and step_vmem_ok(1024, 128, 128)
    assert not step_vmem_ok(8192, 128, 128)
