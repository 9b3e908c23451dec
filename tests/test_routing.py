"""Pattern-routed Pallas lowering (repro.core.routing + kernel patterns).

Covers the ISSUE-5 acceptance criteria: the subsequence matcher over
fusion-group OpSpec chains, feasibility guards, routed-vs-generic
numerics on ``gpt2_block``/``resnet18`` (both the fused-reference backend
and the true Pallas interpret path), the ``CODO_DISABLE_PALLAS`` escape
hatch and its lowering-memo-key coverage, routing decisions riding on
diagnostics and artifacts, and the CLI ``--profile`` routing table.

Since ISSUE 6 routing is cost-gated: tiny unit-test shapes fall below
the predictor's win threshold, so shape-dependent routing tests pin
``CODO_FORCE_PALLAS=1`` to exercise the kernel path deterministically.
The gate itself is covered in ``tests/test_costmodel_routing.py``.
"""

import numpy as np
import pytest

from repro.core import CodoOptions, codo_opt
from repro.core.compiler import main as compiler_main
from repro.core.lowering import (LOWER_CACHE_STATS, clear_lower_cache,
                                 fusion_groups, lower, verify_routing)
from repro.core.routing import (XLA_FUSED, KernelPattern, match_group,
                                pallas_disabled, route_plan)
from repro.kernels import register_all
from repro.kernels.streamfuse import (fused_matmul_chain,
                                      fused_softmax_matmul,
                                      matmul_chain_ref, softmax_matmul_ref)
from repro.models import dataflow_models as dm

register_all()

RNG = np.random.default_rng(7)


def _compile(graph, budget=64):
    return codo_opt(graph, CodoOptions.preset("opt5", budget_units=budget),
                    cache=None)


def _gpt2():
    return _compile(dm.gpt2_block(S=16, D=64))


def _resnet():
    return _compile(dm.resnet18(16))


# --------------------------------------------------------------------------
# The matcher
# --------------------------------------------------------------------------


def _groups_and_impl(compiled):
    impl = compiled.buffer_plan.impl if compiled.buffer_plan else {}
    return fusion_groups(compiled.graph, impl), impl


def test_exact_and_wildcard_matching():
    c = _compile(dm.feed_forward(8, 16))        # matmul -> gelu -> matmul
    groups, impl = _groups_and_impl(c)
    g = max(groups, key=lambda g: len(g.tasks))
    pat = KernelPattern("t", ("matmul", "*ewise", "matmul"),
                        factory=lambda *a: None)
    matches = match_group(c.graph, g.tasks, impl, patterns=[pat])
    assert len(matches) == 1
    ops = [t.op for t in matches[0][1]]
    assert ops == ["matmul", "ewise", "matmul"]   # wildcard took the gelu

    # zero-width wildcard: a bare matmul->matmul chain also matches
    c2 = _compile(dm.three_mm(8))
    groups2, impl2 = _groups_and_impl(c2)
    g2 = max(groups2, key=lambda g: len(g.tasks))
    m2 = match_group(c2.graph, g2.tasks, impl2, patterns=[pat])
    assert m2 and all(len(ts) >= 2 for _p, ts in m2)


def test_matches_never_overlap_and_skip_single_tasks():
    c = _gpt2()
    groups, impl = _groups_and_impl(c)
    g = groups[0]
    claimed = []
    for _p, tasks in match_group(c.graph, g.tasks, impl):
        assert len(tasks) >= 2
        for t in tasks:
            assert t.name not in claimed, "overlapping claims"
            claimed.append(t.name)


def test_feasibility_guards_reject_mv_chains_and_strided_convs():
    # atax is mv->mv: op pattern matches but the spec kinds are not 2-D
    # matmuls, so the mmchain guard declines.
    c = _compile(dm.atax(24, 24))
    groups, impl = _groups_and_impl(c)
    for g in groups:
        for pat, _tasks in match_group(c.graph, g.tasks, impl):
            assert pat.name != "streamfuse.mmchain"
    # stride-2 convs in resnet never route to streamfuse.conv
    c2 = _resnet()
    low = lower(c2, jit=False)
    for g in low.groups:
        for r in g.routes:
            conv = next(c2.graph.task(n) for n in r.tasks
                        if c2.graph.task(n).op == "conv")
            assert int(conv.spec.attrs.get("stride", 1)) == 1


def test_chain_operand_reuse_does_not_route():
    """A task consuming the chain value through a *second* operand slot
    (p @ p) cannot be folded into a kernel that never emits the interior
    — such graphs must stay on the generic path and still execute."""
    from repro.core import frontend as F

    def pp(s):
        p = F.softmax(s)
        return F.matmul(p, p)                # softmax -> matmul, but v is p

    c = _compile(F.trace(pp, (8, 8), name="pp"))
    low = lower(c, jit=False)
    assert all("softmaxmm" not in r.kernel
               for g in low.groups for r in g.routes)
    env = dm.random_inputs(c.graph)
    low(env)                                 # executes — no KeyError
    verify_routing(c, env)

    def hh(a, w):
        h = F.matmul(a, w)
        return F.matmul(h, h)                # (a@w) @ (a@w)

    c2 = _compile(F.trace(hh, (8, 8), (8, 8), name="hh"))
    low2 = lower(c2, jit=False)
    assert all("mmchain" not in r.kernel
               for g in low2.groups for r in g.routes)
    verify_routing(c2, dm.random_inputs(c2.graph))


def test_wildcard_cannot_anchor_pattern():
    with pytest.raises(ValueError, match="wildcard"):
        KernelPattern("bad", ("*ewise", "matmul"), factory=lambda *a: None)


# --------------------------------------------------------------------------
# Acceptance: gpt2_block and resnet18 route and verify
# --------------------------------------------------------------------------


def test_gpt2_block_routes_and_verifies(monkeypatch):
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _gpt2()
    low = lower(c, jit=False)
    routed = [g for g in low.groups if g.routes]
    assert routed, "gpt2_block must route at least one fusion group"
    kernels = {r.kernel for g in routed for r in g.routes}
    assert "streamfuse.mmchain" in kernels           # the FFN chain
    # The full attention chain goes to flashattn — which supersedes the
    # softmaxmm tail (matmul -> scale -> softmax -> matmul claimed whole).
    assert "flashattn.mha" in kernels
    assert "streamfuse.softmaxmm" not in kernels
    env = dm.random_inputs(c.graph)
    verify_routing(c, env, rtol=3e-4, atol=3e-4)
    # the decision rides on the diagnostics, with the gate's estimates
    entries = c.diagnostics.group_kernels.values()
    assert any(e["kernel"] != XLA_FUSED for e in entries)
    assert all(e["decision"] and "predicted_routed_cycles" in e
               for e in entries)
    assert "pallas-routed" in c.diagnostics.summary()


def test_resnet18_routes_and_verifies():
    c = _resnet()
    low = lower(c, jit=False)
    conv_routed = [g for g in low.groups
                   if any(r.kernel == "streamfuse.conv" for r in g.routes)]
    assert conv_routed, "resnet18 must route conv chains"
    env = dm.random_inputs(c.graph)
    verify_routing(c, env, rtol=3e-4, atol=3e-4)


def test_routed_interior_buffers_never_materialize():
    c = _gpt2()
    low = lower(c, jit=False)
    interior = {c.graph.task(n).writes[0].buffer
                for g in low.groups for r in g.routes for n in r.tasks[:-1]}
    assert interior.isdisjoint(low.materialized)
    out = low(dm.random_inputs(c.graph))
    assert set(out) == {b.name for b in c.graph.outputs()}


def test_true_pallas_interpret_path(monkeypatch):
    """CODO_PALLAS_INTERPRET=1 runs the real Pallas kernel bodies (in
    interpret mode on CPU) through the routed lowering — the mmchain and
    flashattn kernels via gpt2, the conv kernel via the Fig. 2 chain."""
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _gpt2()
    env = dm.random_inputs(c.graph)
    routed = verify_routing(c, env, rtol=3e-4, atol=3e-4)
    assert any(g.routes for g in routed.groups)

    c2 = _compile(dm.conv3_block(1, 3, 10))
    routed2 = verify_routing(c2, dm.random_inputs(c2.graph),
                             rtol=3e-4, atol=3e-4)
    assert any(r.kernel == "streamfuse.conv"
               for g in routed2.groups for r in g.routes)


# --------------------------------------------------------------------------
# The kernels themselves, against their refs (interpret mode)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 16, 24, 12), (16, 32, 8, 16)])
def test_fused_matmul_chain_matches_ref(shape):
    import jax.nn
    M, K, N1, N2 = shape
    a = RNG.standard_normal((M, K)).astype(np.float32)
    w1 = RNG.standard_normal((K, N1)).astype(np.float32)
    w2 = RNG.standard_normal((N1, N2)).astype(np.float32)
    for ew in ((), jax.nn.gelu):
        got = fused_matmul_chain(a, w1, w2, ew=ew, interpret=True)
        want = matmul_chain_ref(a, w1, w2, ew)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(8, 24, 12), (16, 32, 8)])
def test_fused_softmax_matmul_matches_ref(shape):
    M, K, N = shape
    s = (RNG.standard_normal((M, K)) * 3).astype(np.float32)
    v = RNG.standard_normal((K, N)).astype(np.float32)
    got = fused_softmax_matmul(s, v, block_k=8, interpret=True)
    want = softmax_matmul_ref(s, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# --------------------------------------------------------------------------
# Escape hatch + memo key (satellite: stale-program audit)
# --------------------------------------------------------------------------


def test_disable_pallas_routes_everything_to_xla(monkeypatch):
    monkeypatch.setenv("CODO_DISABLE_PALLAS", "1")
    assert pallas_disabled()
    c = _gpt2()
    low = lower(c, jit=False)
    assert all(g.kernel == XLA_FUSED and not g.routes for g in low.groups)
    verify_routing(c, dm.random_inputs(c.graph))   # trivially equal
    assert all(e["kernel"] == XLA_FUSED
               for e in c.diagnostics.group_kernels.values())


def test_flipping_disable_flag_relowers(monkeypatch):
    """Toggling CODO_DISABLE_PALLAS must never serve a memoized program
    built under the other setting — the flag is part of the memo key."""
    monkeypatch.delenv("CODO_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _gpt2()
    lower(c, jit=False)          # assigns fused_group ids (hash settles)
    clear_lower_cache()
    low_on = lower(c, jit=False)
    assert any(g.routes for g in low_on.groups)
    assert LOWER_CACHE_STATS["misses"] == 1
    lower(c, jit=False)                      # same key: a hit
    assert LOWER_CACHE_STATS["hits"] == 1

    monkeypatch.setenv("CODO_DISABLE_PALLAS", "1")
    low_off = lower(c, jit=False)            # flipped: must re-lower
    assert LOWER_CACHE_STATS["misses"] == 2
    assert all(not g.routes for g in low_off.groups)

    monkeypatch.delenv("CODO_DISABLE_PALLAS")
    low_back = lower(c, jit=False)           # back: the routed entry again
    assert LOWER_CACHE_STATS["hits"] == 2
    assert any(g.routes for g in low_back.groups)


def test_interpret_flag_is_in_memo_key(monkeypatch):
    monkeypatch.delenv("CODO_PALLAS_INTERPRET", raising=False)
    c = _gpt2()
    lower(c, jit=False)          # settle fused_group ids
    clear_lower_cache()
    lower(c, jit=False)
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    lower(c, jit=False)
    assert LOWER_CACHE_STATS["misses"] == 2


# --------------------------------------------------------------------------
# Routing rides on artifacts (v1.2) and the CLI --profile table
# --------------------------------------------------------------------------


def test_artifact_records_group_kernels(monkeypatch):
    from repro.core import export_artifact, import_artifact
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _gpt2()
    lower(c, jit=False)
    doc = export_artifact(c)
    assert doc["schema_version"] == "1.5"
    kernels = doc["fusion"]["kernels"]
    assert len(kernels) == len(doc["fusion"]["groups"])
    assert any(k.startswith("pallas:") for k in kernels)
    restored = import_artifact(doc)          # same registry: no drift warn
    assert restored.diagnostics.group_kernels == c.diagnostics.group_kernels


def test_route_plan_is_jax_free_view(monkeypatch):
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _gpt2()
    impl = c.buffer_plan.impl if c.buffer_plan else {}
    plan = route_plan(c.graph, impl)
    assert any(p["kernel"].startswith("pallas:") for p in plan)
    assert all(set(p) == {"gid", "tasks", "kernel", "routes", "rejected"}
               for p in plan)


def test_cli_profile_prints_routing_table(tmp_path, capsys):
    rc = compiler_main(["--configs", "gpt2_block", "--opts", "opt5",
                        "--executor", "thread", "--jobs", "1", "--no-cache",
                        "--seq", "16", "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "kernel routing" in out
    assert "gpt2_block/opt5: 1/1 groups pallas-routed" in out
    assert "streamfuse.mmchain" in out


def test_interpret_flag_ignored_on_tpu_backend(monkeypatch):
    """On a TPU the compiled kernel is the real path: the CI interpret
    switch warns and has no effect there, and is honored elsewhere."""
    import jax
    from repro.core.routing import pallas_interpret_forced
    from repro.kernels.common import kernel_mode
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    assert pallas_interpret_forced() and kernel_mode() == "interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.warns(RuntimeWarning, match="ignored on a TPU"):
        assert not pallas_interpret_forced()
    with pytest.warns(RuntimeWarning):
        assert kernel_mode() == "pallas"
