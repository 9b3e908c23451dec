"""Cost-gated routing + measured autotuning (the ISSUE-6 tentpole).

Covers the latency predictor against the recorded routing bench (rank
agreement, not absolute cycles), the CPU softmaxmm fallback the bench
motivated, the ``tuned > forced > predicted`` decision precedence with
its env overrides, the tuning database's ride through artifact v1.2 into
a fresh interpreter, and the lowering memo key's sensitivity to tuning
changes.  Kernel numerics and the matcher itself live in
``tests/test_routing.py``.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import CodoOptions, codo_opt
from repro.core.costmodel import estimate_chain, routing_backend, \
    routing_params
from repro.core.lowering import (LOWER_CACHE_STATS, clear_lower_cache,
                                 fusion_groups, lower)
from repro.core.routing import ROUTED_DECISIONS, XLA_FUSED, match_group
from repro.core.tuning import (TuningRecord, autotune_compiled,
                               chain_signature, default_tuning_db,
                               reset_default_tuning_db)
from repro.kernels import register_all
from repro.models import dataflow_models as dm

register_all()

REPO = Path(__file__).resolve().parents[1]
BENCH_JSON = REPO / "results" / "bench" / "routing_groups.json"


@pytest.fixture(autouse=True)
def _clean_tuning_db():
    """Measured decisions override the predictor, so every test here gets
    (and leaves behind) an empty process tuning database."""
    reset_default_tuning_db()
    yield
    reset_default_tuning_db()


def _compile(graph, budget=64):
    return codo_opt(graph, CodoOptions.preset("opt5", budget_units=budget),
                    cache=None)


# --------------------------------------------------------------------------
# The predictor vs the recorded bench
# --------------------------------------------------------------------------


@pytest.mark.skipif(not BENCH_JSON.exists(),
                    reason="no recorded routing bench")
def test_predictor_ranks_chains_like_recorded_bench():
    """The gate doesn't need cycle-accurate latencies — it needs the
    *ordering* of chains by cost to agree with what the machine measured.

    Judged as pairwise rank concordance within each workload, and only
    over pairs where both sides show a real margin: resnet stages are
    constant-FLOPs by design, so the model prices them as near-ties that
    CPU wall-clock (which favors large-spatial layers) legitimately
    scrambles; and the cycle→ms scale differs per op family, so
    cross-workload pairs are not comparable."""
    doc = json.loads(BENCH_JSON.read_text())
    quick = bool(doc.get("quick"))
    builds = {
        "gpt2_block": (lambda: dm.gpt2_block(S=64)) if quick
        else (lambda: dm.gpt2_block()),
        "resnet18": lambda: dm.resnet18(32),
    }
    predicted = {}
    for wname, build in builds.items():
        c = codo_opt(build(), CodoOptions.preset("opt5"), cache=None)
        impl = c.buffer_plan.impl if c.buffer_plan else {}
        for g in fusion_groups(c.graph, impl):
            for pat, tasks in match_group(c.graph, g.tasks, impl):
                est = estimate_chain(c.graph, tasks, pat.name)
                key = (wname, tuple(t.name for t in tasks))
                predicted[key] = est.generic_cycles
    points = []
    for r in doc["records"]:
        key = (r["workload"], tuple(r["tasks"]))
        if key in predicted:
            points.append((r["workload"], predicted[key],
                           float(r["xla_ms"])))
    assert len(points) >= 6, "bench records no longer line up with matcher"

    judged = concordant = 0
    for i in range(len(points)):
        for j in range(i + 1, len(points)):
            (wa, pa, ma), (wb, pb, mb) = points[i], points[j]
            if wa != wb:
                continue
            if max(pa, pb) < 1.2 * min(pa, pb):      # predicted near-tie
                continue
            if max(ma, mb) < 1.3 * min(ma, mb):      # measured noise band
                continue
            judged += 1
            concordant += (pa > pb) == (ma > mb)
    assert judged >= 5, f"only {judged} decisive pairs"
    assert concordant / judged >= 0.8, \
        f"predictor agrees on {concordant}/{judged} decisive pairs"


def test_softmaxmm_tail_stays_generic_on_cpu(monkeypatch):
    """The satellite bugfix, pinned: the bench measures the softmaxmm
    kernel at ~0.97x on CPU, so the calibrated gate must route the
    attention tail to generic XLA there — at any size.  In gpt2_block the
    full-chain ``flashattn.mha`` now supersedes this tail (see
    test_flashattn_supersedes_softmaxmm_in_gpt2), so the bare tail is
    exercised on a graph whose chain *starts* at the softmax."""
    monkeypatch.delenv("CODO_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("CODO_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("CODO_ROUTING_CALIBRATION", raising=False)
    monkeypatch.setenv("CODO_BACKEND", "cpu")
    # CPU has no spill/overlap terms, so the win condition reduces to
    # eff * (1 + slack) > 1; softmaxmm's calibrated 0.97 keeps it losing
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


def test_calibration_env_knob_refits_efficiency(monkeypatch, tmp_path):
    doc = {"backend": "cpu", "records": [
        {"kernel": "streamfuse.softmaxmm", "speedup": 1.5},
        {"kernel": "streamfuse.softmaxmm", "speedup": 1.5},
    ]}
    path = tmp_path / "calib.json"
    path.write_text(json.dumps(doc))
    monkeypatch.setenv("CODO_ROUTING_CALIBRATION", str(path))
    p = routing_params("cpu")
    assert p.eff("streamfuse.softmaxmm") == pytest.approx(1.5, rel=1e-3)
    # patterns absent from the document keep their defaults
    assert p.eff("streamfuse.conv") == pytest.approx(0.99)


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


def test_tuning_db_change_flips_memo_key_and_decision():
    """A measured entry must (a) override the predictor's verdict and
    (b) change the lowering memo key, so stale programs built before the
    measurement can never be served after it."""
    c = _compile(dm.feed_forward(16, 32))
    lower(c, jit=False)          # assigns fused_group ids (hash settles)
    clear_lower_cache()
    low = lower(c, jit=False)
    assert LOWER_CACHE_STATS["misses"] == 1
    rej = [r for g in low.groups for r in g.rejected
           if r.kernel == "streamfuse.mmchain"]
    assert rej and rej[0].decision == "predicted-loss"
    lower(c, jit=False)                      # same key: a hit
    assert LOWER_CACHE_STATS["hits"] == 1

    tasks = [c.graph.task(n) for n in rej[0].tasks]
    default_tuning_db().update(TuningRecord(
        signature=chain_signature(c.graph, tasks),
        backend=routing_backend(), hw=c.options.hw.name,
        pattern="streamfuse.mmchain", choice="pallas",
        routed_ms=1.0, generic_ms=2.0))
    low2 = lower(c, jit=False)               # digest changed: re-lower
    assert LOWER_CACHE_STATS["misses"] == 2
    tuned = [r for g in low2.groups for r in g.routes
             if r.kernel == "streamfuse.mmchain"]
    assert tuned and tuned[0].decision == "tuned"
    assert tuned[0].measured_speedup == pytest.approx(2.0)

    reset_default_tuning_db()                # back to the empty-db digest:
    low3 = lower(c, jit=False)               # the pre-tuning entry is reused
    assert LOWER_CACHE_STATS["hits"] == 2
    assert all(r.kernel != "streamfuse.mmchain"
               for g in low3.groups for r in g.routes)


# --------------------------------------------------------------------------
# Measured autotune riding artifact v1.2 into a fresh interpreter
# --------------------------------------------------------------------------


def _fresh_interpreter(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    for k in ("CODO_TUNING_DB", "CODO_FORCE_PALLAS", "CODO_DISABLE_PALLAS"):
        env.pop(k, None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env)


def test_tuning_roundtrips_through_artifact_in_fresh_interpreter(tmp_path):
    from repro.core import export_artifact
    c = _compile(dm.feed_forward(16, 32))
    lower(c, jit=False)
    records = autotune_compiled(c, repeats=2, warmup=1)
    assert records and len(default_tuning_db()) >= 1
    assert all(r.choice in ("pallas", XLA_FUSED) for r in records)

    doc = export_artifact(c)
    assert doc["schema_version"] == "1.5"
    assert doc["tuning"] and len(doc["tuning"]["entries"]) >= 1
    path = tmp_path / "ff.json"
    path.write_text(json.dumps(doc))

    proc = _fresh_interpreter(f"""
        import json
        from repro.core import import_artifact
        from repro.core.lowering import lower
        from repro.core.tuning import default_tuning_db
        from repro.models.dataflow_models import random_inputs

        doc = json.loads(open({str(path)!r}).read())
        assert len(default_tuning_db()) == 0
        c = import_artifact(doc)
        db = default_tuning_db()
        want = {{e["signature"] for e in doc["tuning"]["entries"]}}
        got = {{r.signature for r in db.entries.values()}}
        assert want <= got, (want, got)
        # the imported measurement drives routing in this process too
        low = lower(c, jit=False)
        decisions = {{r.decision for g in low.groups
                      for r in (*g.routes, *g.rejected)}}
        assert decisions & {{"tuned", "tuned-generic"}}, decisions
        low(random_inputs(c.graph))              # still executes
        print("ROUNDTRIP-OK", len(db))
    """)
    assert proc.returncode == 0, proc.stderr
    assert "ROUNDTRIP-OK" in proc.stdout


def test_gate_retry_remeasures_only_offenders(monkeypatch):
    """The CI gate re-times first-pass offenders solo at a higher
    best-of count and judges the fresh numbers — a noise blip converges
    back within tolerance, a real regression fails twice."""
    from benchmarks import routing_bench as rb

    def rec(workload, gid, kernel, speedup, routed=True):
        return {"workload": workload, "gid": gid, "kernel": kernel,
                "tasks": ["a", "b"], "decision": "predicted-win",
                "routed": routed, "speedup": speedup,
                "pallas_ms": 1.0, "xla_ms": speedup,
                "predicted_speedup": 1.0,
                "predicted_routed_cycles": 1.0,
                "predicted_generic_cycles": 1.0}

    doc = {"backend": "cpu", "tolerance": 0.05, "quick": True,
           "records": [rec("resnet18", 1, "streamfuse.conv", 1.02),
                       rec("resnet18", 6, "streamfuse.conv", 0.92),
                       rec("gpt2_block", 0, "streamfuse.softmaxmm",
                           0.80, routed=False)]}
    # Only the routed under-tolerance record is an offender; the
    # rejected softmaxmm chain is measured but never judged.
    assert len(rb.check_gate(doc)) == 1

    seen = []

    def fake_bench(name, build, *, warmup, reps, only=None):
        seen.append((name, reps, only))
        return [rec(name, gid, kernel, 0.99)
                for gid, kernel, _tasks in sorted(only)]

    monkeypatch.setattr(rb, "bench_workload", fake_bench)
    doc = rb.remeasure_offenders(doc)
    # One solo re-run, offender only, at the recheck best-of count.
    assert seen == [("resnet18", rb.RECHECK_REPS,
                     {(6, "streamfuse.conv", ("a", "b"))})]
    by_gid = {r["gid"]: r for r in doc["records"]
              if r["workload"] == "resnet18"}
    assert by_gid[6]["speedup"] == 0.99      # patched in
    assert by_gid[1]["speedup"] == 1.02      # untouched
    assert rb.check_gate(doc) == []

    # A repeat offender stays failed.
    doc["records"][1]["speedup"] = 0.90
    monkeypatch.setattr(
        rb, "bench_workload",
        lambda name, build, *, warmup, reps, only=None:
        [rec(name, gid, kernel, 0.90)
         for gid, kernel, _tasks in sorted(only)])
    doc = rb.remeasure_offenders(doc)
    assert len(rb.check_gate(doc)) == 1


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
