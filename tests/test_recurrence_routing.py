"""Attention/recurrence family routing + diagnostics (the ISSUE-7 tentpole).

Covers the full-chain ``flashattn.mha`` superseding the old softmaxmm
tail inside gpt2_block, routed-vs-generic numerics for the three traced
recurrence workloads (reference backend and the true Pallas interpret
path), their ``rejected[]``/``RouteDecision`` diagnostics under the
un-forced CPU cost gate, the TPU parameterisation that flips those same
chains to predicted wins, and the pattern-registry epoch riding the
lowering memo key.  Kernel numerics live in ``tests/test_kernels.py``;
the gate's constants in ``tests/test_costmodel_routing.py``.
"""

import pytest

from repro.core import CodoOptions, codo_opt
from repro.core.costmodel import DEFAULT_ROUTING_PARAMS, estimate_chain
from repro.core.lowering import (LOWER_CACHE_STATS, clear_lower_cache,
                                 fusion_groups, lower, verify_routing)
from repro.core.routing import (KernelPattern, match_group,
                                register_kernel_pattern, routing_epoch)
from repro.kernels import register_all
from repro.models import dataflow_models as dm

register_all()

# workload builder -> the kernel its recurrence chain must route to
FAMILIES = [
    ("mha_batched", dm.mha_batched, "flashattn.mha"),
    ("rglru_block", dm.rglru_block, "rglru.scan"),
    ("ssd_block", dm.ssd_block, "ssd.scan"),
]


def _compile(graph, budget=64):
    return codo_opt(graph, CodoOptions.preset("opt5", budget_units=budget),
                    cache=None)


def _matches(compiled):
    impl = compiled.buffer_plan.impl if compiled.buffer_plan else {}
    out = []
    for g in fusion_groups(compiled.graph, impl):
        out.extend(match_group(compiled.graph, g.tasks, impl))
    return out


# --------------------------------------------------------------------------
# Longest-match-first: flashattn supersedes the softmaxmm tail
# --------------------------------------------------------------------------


def test_flashattn_supersedes_softmaxmm_in_gpt2():
    """gpt2's attention chain (matmul -> scale -> softmax -> matmul) must
    be claimed whole by flashattn.mha; the shorter softmaxmm tail and the
    mmchain starting at the q-projection would both overlap it and must
    lose the longest-match tie-break."""
    c = _compile(dm.gpt2_block(S=16, D=64))
    matched = _matches(c)
    names = {pat.name for pat, _tasks in matched}
    assert "flashattn.mha" in names
    assert "streamfuse.softmaxmm" not in names
    chain = next(ts for pat, ts in matched if pat.name == "flashattn.mha")
    assert [t.op for t in chain] == ["matmul", "ewise", "softmax", "matmul"]
    # the FFN mmchain survives on non-overlapping tasks
    assert "streamfuse.mmchain" in names
    ff = next(ts for pat, ts in matched
              if pat.name == "streamfuse.mmchain")
    assert {t.name for t in ff}.isdisjoint({t.name for t in chain})


def test_single_task_scan_chains_match():
    """The scan patterns opt into single-task chains (allow_single);
    everything else keeps the >= 2 floor."""
    c = _compile(dm.rglru_block(B=1, S=8, D=8))
    matched = _matches(c)
    scans = [ts for pat, ts in matched if pat.name == "rglru.scan"]
    assert scans and len(scans[0]) == 1 and scans[0][0].op == "scan"


# --------------------------------------------------------------------------
# Routed == generic, per family (reference backend + true Pallas interpret)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("wname,build,kernel", FAMILIES)
def test_recurrence_family_routes_and_verifies(monkeypatch, wname, build,
                                               kernel):
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = _compile(build())
    low = lower(c, jit=False)
    routed = {r.kernel for g in low.groups for r in g.routes}
    assert kernel in routed, f"{wname} must route its chain to {kernel}"
    verify_routing(c, dm.random_inputs(c.graph), rtol=3e-4, atol=3e-4)
    entries = c.diagnostics.group_kernels.values()
    hits = [rr for e in entries for rr in e["routes"]
            if rr["kernel"] == kernel]
    assert hits and all(rr["decision"] == "forced" for rr in hits)


@pytest.mark.parametrize("wname,build,kernel", FAMILIES)
def test_recurrence_family_true_pallas_interpret(monkeypatch, wname, build,
                                                 kernel):
    """CODO_PALLAS_INTERPRET=1 swaps the jnp references for the real
    Pallas kernel bodies (interpret mode on CPU) — parity must hold
    through the routed lowering for every family."""
    monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")
    c = _compile(build())
    routed = verify_routing(c, dm.random_inputs(c.graph),
                            rtol=3e-4, atol=3e-4)
    assert any(r.kernel == kernel
               for g in routed.groups for r in g.routes)


# --------------------------------------------------------------------------
# Diagnostics under the un-forced CPU gate
# --------------------------------------------------------------------------


@pytest.mark.parametrize("wname,build,kernel", FAMILIES)
def test_cpu_gate_rejects_with_full_diagnostics(monkeypatch, wname, build,
                                                kernel):
    """On CPU the scan kernels' constants sit below break-even and the
    default mha_batched shape sits under the flashattn win threshold, so
    each chain lands in ``rejected[]`` as a fully-priced RouteDecision —
    not silently dropped by the matcher."""
    monkeypatch.delenv("CODO_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("CODO_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("CODO_BACKEND", "cpu")
    c = _compile(build())
    low = lower(c, jit=False)
    assert all(r.kernel != kernel for g in low.groups for r in g.routes)
    rej = [r for g in low.groups for r in g.rejected if r.kernel == kernel]
    assert rej, f"{wname}: the {kernel} chain must still match structurally"
    for r in rej:
        assert r.decision == "predicted-loss" and not r.routed
        assert r.predicted_routed_cycles > 0
        assert r.predicted_generic_cycles > 0
        assert r.predicted_generic_cycles < r.predicted_routed_cycles
        assert all(c.graph.task(n) is not None for n in r.tasks)
    # ...and the verdict rides on the diagnostics
    entries = c.diagnostics.group_kernels.values()
    assert any(any(rr["kernel"] == kernel
                   and rr["decision"] == "predicted-loss"
                   and "predicted_generic_cycles" in rr
                   for rr in e["rejected"]) for e in entries)


@pytest.mark.parametrize("wname,build,kernel", [
    # nightly-bench sizes: big enough to amortize the fixed launch term
    ("mha_batched", lambda: dm.mha_batched(), "flashattn.mha"),
    ("rglru_block", lambda: dm.rglru_block(B=4, S=256, D=128), "rglru.scan"),
    ("ssd_block", lambda: dm.ssd_block(nc=16, BH=16, P=32, N=32), "ssd.scan"),
])
def test_tpu_params_predict_win_for_recurrences(wname, build, kernel):
    """Under the TPU gate parameters (pipelined VMEM stages, interior HBM
    round-trips on the generic path) the same chains price as wins — the
    CPU rejection above is a backend verdict, not a structural one."""
    c = _compile(build())
    impl = c.buffer_plan.impl if c.buffer_plan else {}
    chains = [ts for g in fusion_groups(c.graph, impl)
              for pat, ts in match_group(c.graph, g.tasks, impl)
              if pat.name == kernel]
    assert chains
    est = estimate_chain(c.graph, chains[0], kernel,
                         params=DEFAULT_ROUTING_PARAMS["tpu"])
    assert est.win and est.predicted_speedup > 1.0


# --------------------------------------------------------------------------
# Registry epoch rides the lowering memo key
# --------------------------------------------------------------------------


def test_pattern_registration_flips_memo_key():
    """Registering a pattern bumps the routing epoch, which is part of
    the lowering memo key — a program lowered against the old registry is
    never served after the registry changes."""
    c = _compile(dm.rglru_block(B=1, S=16, D=8))
    lower(c, jit=False)          # assigns fused_group ids (hash settles)
    clear_lower_cache()
    lower(c, jit=False)
    assert LOWER_CACHE_STATS["misses"] == 1
    lower(c, jit=False)                      # same key: a hit
    assert LOWER_CACHE_STATS["hits"] == 1

    before = routing_epoch()
    # an op kind no graph produces: match-inert, but epoch still bumps
    register_kernel_pattern(KernelPattern(
        "test.epoch-probe", ("matmul", "never_op"),
        factory=lambda *a, **k: None))
    assert routing_epoch() == before + 1
    lower(c, jit=False)                      # new epoch: must re-lower
    assert LOWER_CACHE_STATS["misses"] == 2
