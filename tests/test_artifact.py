"""Portable compiled-design artifacts (docs/artifact_format.md).

Covers the full contract: lossless round-trips (structural hash, cache
key, numerics), fresh-interpreter imports of exported ResNet/GPT-2
designs that lower + execute + verify, strict validation with
path-qualified errors, the forward-compat policy (unknown fields warn,
version-major mismatch fails), integrity/fusion cross-checks, the disk
cache's JSON mirror, and the compiler CLI verbs.
"""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core import (ArtifactError, ArtifactWarning, CodoOptions,
                        CompileCache, artifact_summary, codo_opt,
                        export_artifact, import_artifact, lower,
                        lower_artifact, validate_artifact, verify_lowering)
from repro.core.compiler import main as compiler_main
from repro.core.compiler import profile_table
from repro.models import dataflow_models as dm

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _compile_block(budget=64):
    return codo_opt(dm.residual_block(1, 8, 12),
                    CodoOptions(budget_units=budget), cache=None)


# --------------------------------------------------------------------------
# Round-trip fidelity
# --------------------------------------------------------------------------


def test_roundtrip_preserves_structure_and_numerics(tmp_path):
    c = _compile_block()
    path = tmp_path / "design.json"
    doc = export_artifact(c, path)
    assert path.exists() and json.loads(path.read_text()) == doc

    r = import_artifact(path)
    assert r.graph.structural_hash() == c.graph.structural_hash()
    assert r.options == c.options
    assert r.options.cache_key() == c.options.cache_key()
    assert r.buffer_plan.impl == c.buffer_plan.impl
    assert r.transfer_plan.channel_of == c.transfer_plan.channel_of
    assert r.schedule_report.degrees == c.schedule_report.degrees
    assert list(r.schedule_report.stage_latencies) == \
        list(c.schedule_report.stage_latencies)
    assert r.diagnostics.pass_names == c.diagnostics.pass_names
    # costs recompute identically from the reconstructed graph
    np.testing.assert_allclose(r.final.total_cycles, c.final.total_cycles)
    np.testing.assert_allclose(r.speedup, c.speedup)

    # and the imported design executes + verifies against the oracle
    src = dm.residual_block(1, 8, 12)
    env = dm.random_inputs(src)
    verify_lowering(src, r, env, rtol=3e-4, atol=3e-4)


def test_reexport_is_idempotent(tmp_path):
    c = _compile_block()
    doc = export_artifact(c)
    doc2 = export_artifact(import_artifact(doc))
    # diagnostics/cost are carried through, graph bytes identical
    assert doc2["graph"] == doc["graph"]
    assert doc2["integrity"] == doc["integrity"]
    assert doc2["fusion"] == doc["fusion"]


def test_lower_artifact_shortcut(tmp_path):
    c = _compile_block()
    path = tmp_path / "d.json"
    export_artifact(c, path)
    low = lower_artifact(path, jit=False)
    env = dm.random_inputs(dm.residual_block(1, 8, 12))
    out = low(env)
    assert set(out) == {b.name for b in c.graph.outputs()}


def test_export_rejects_closure_tasks():
    from repro.core import DataflowGraph, ewise_task
    g = DataflowGraph("closure")
    g.buffer("x", (4,), kind="input")
    g.buffer("y", (4,), kind="output")
    g.add_task(ewise_task("t", "y", ["x"], (4,), fn=lambda env: {"y": env["x"]}))
    c = codo_opt(g, cache=None)
    with pytest.raises(ArtifactError, match="closure"):
        export_artifact(c)


# --------------------------------------------------------------------------
# Fresh-interpreter round-trips (the paper's hand-off property)
# --------------------------------------------------------------------------


def _fresh_interpreter(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600, env=env)


@pytest.mark.parametrize("workload", ["resnet", "gpt2"])
def test_fresh_interpreter_import_executes_and_verifies(tmp_path, workload):
    if workload == "resnet":
        build = "dm.resnet18(16)"
        g = dm.resnet18(16)
    else:
        from repro.core.compiler import batch_workloads
        build = 'batch_workloads(seq=8)["gpt2-medium"]()'
        g = batch_workloads(seq=8)["gpt2-medium"]()
    path = tmp_path / f"{workload}.json"
    c = codo_opt(g, CodoOptions(budget_units=64), cache=None)
    export_artifact(c, path)

    proc = _fresh_interpreter(f"""
        from repro.core import import_artifact, lower, verify_lowering
        from repro.core.compiler import batch_workloads
        from repro.core.passes import PASS_RUN_COUNTS
        from repro.models import dataflow_models as dm

        c = import_artifact({str(path)!r})
        assert not PASS_RUN_COUNTS, "import must not run any compile pass"
        assert all(t.fn is not None for t in c.graph.tasks)
        src = {build}
        env = dm.random_inputs(src)
        out = lower(c, jit=False)(env)
        assert set(out) == {{b.name for b in c.graph.outputs()}}
        verify_lowering(src, c, env, rtol=3e-4, atol=3e-4)
        print("ARTIFACT_IMPORT_OK", c.final.total_cycles)
    """)
    assert proc.returncode == 0, proc.stderr
    assert "ARTIFACT_IMPORT_OK" in proc.stdout
    # the cost model reproduces the exporter's estimate exactly
    reported = float(proc.stdout.split("ARTIFACT_IMPORT_OK")[1].split()[0])
    np.testing.assert_allclose(reported, c.final.total_cycles, rtol=1e-9)


# --------------------------------------------------------------------------
# Validation / compatibility policy
# --------------------------------------------------------------------------


def test_corrupted_artifacts_fail_with_paths(tmp_path):
    doc = export_artifact(_compile_block())

    bad = json.loads(json.dumps(doc))
    del bad["graph"]["tasks"][0]["loops"]
    bad["graph"]["buffers"][0]["shape"] = "oops"
    bad["graph"]["buffers"][1]["kind"] = "wat"
    with pytest.raises(ArtifactError) as e:
        validate_artifact(bad)
    msg = str(e.value)
    assert "graph.tasks[0].loops: missing required field" in msg
    assert "graph.buffers[0].shape: expected list, got str" in msg
    assert "graph.buffers[1].kind" in msg

    # dangling access reference
    bad = json.loads(json.dumps(doc))
    bad["graph"]["tasks"][0]["reads"][0]["buffer"] = "ghost"
    with pytest.raises(ArtifactError, match="not a declared graph buffer"):
        validate_artifact(bad)

    # truncated file
    trunc = tmp_path / "t.json"
    trunc.write_text(json.dumps(doc)[:80])
    with pytest.raises(ArtifactError, match="not valid JSON"):
        import_artifact(trunc)

    # not an artifact at all
    with pytest.raises(ArtifactError, match="JSON object"):
        validate_artifact([1, 2, 3])


def test_version_policy():
    doc = export_artifact(_compile_block())

    old = dict(doc, schema_version="2.0")   # different major: fail
    with pytest.raises(ArtifactError, match="schema_version"):
        import_artifact(old)

    with pytest.raises(ArtifactError, match="major"):
        validate_artifact(dict(doc, schema_version="0.9"))

    newer = dict(doc, schema_version="1.7")  # newer minor: warn + proceed
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        import_artifact(newer)
    assert any("newer" in str(x.message) for x in w
               if issubclass(x.category, ArtifactWarning))

    with pytest.raises(ArtifactError, match="major.*minor"):
        validate_artifact(dict(doc, schema_version="one"))


def test_unknown_fields_warn_everywhere():
    doc = export_artifact(_compile_block())
    mod = json.loads(json.dumps(doc))
    mod["novel_top"] = 1
    mod["graph"]["tasks"][0]["novel_task_field"] = True
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        import_artifact(mod)
    msgs = [str(x.message) for x in w if issubclass(x.category, ArtifactWarning)]
    assert any("artifact.novel_top" in m for m in msgs)
    assert any("graph.tasks[0].novel_task_field" in m for m in msgs)


def test_old_tuning_section_is_read_and_ignored():
    """Documents from exporters that still wrote measured routing records
    (the optional v1.2 ``tuning`` section) validate with a note, route
    exactly as the clean document does, and re-export without it."""
    from repro.kernels import register_all
    register_all()
    c = codo_opt(dm.feed_forward(64, 256), CodoOptions(budget_units=64),
                 cache=None)
    lower(c, jit=False)
    clean = export_artifact(c)
    assert "tuning" not in clean
    old = json.loads(json.dumps(clean))
    old["tuning"] = {"entries": [
        {"signature": "0123456789abcdef", "backend": "cpu", "hw": "v5e",
         "pattern": "streamfuse.mmchain", "choice": "xla-fused",
         "tile": {"block_m": 64}, "routed_ms": 2.0, "generic_ms": 1.0,
         "workload": clean["graph"]["name"], "tasks": []}]}

    notes = validate_artifact(old)
    assert [n for n in notes if n.startswith("tuning:")]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        imported = import_artifact(old)
    assert any(str(x.message).startswith("tuning:") for x in w
               if issubclass(x.category, ArtifactWarning))

    def routes(design):
        return [(r.kernel, list(r.tasks), r.decision)
                for g in lower(design, jit=False).groups
                for r in (*g.routes, *g.rejected)]

    want = routes(import_artifact(clean))
    assert want and routes(imported) == want
    assert "tuning" not in export_artifact(imported)


def test_integrity_and_fusion_cross_checks():
    doc = export_artifact(_compile_block())

    tampered = json.loads(json.dumps(doc))
    tampered["graph"]["tasks"][0]["loops"][0]["trip"] += 1
    with pytest.raises(ArtifactError, match="integrity"):
        import_artifact(tampered)
    # ... unless the edit is deliberate
    c = import_artifact(tampered, check_integrity=False)
    assert c.graph.tasks[0].loops[0].trip == \
        tampered["graph"]["tasks"][0]["loops"][0]["trip"]

    inconsistent = json.loads(json.dumps(doc))
    inconsistent["fusion"]["groups"] = [[t["name"] for t in
                                        inconsistent["graph"]["tasks"]]]
    with pytest.raises(ArtifactError, match="fusion"):
        import_artifact(inconsistent)


def test_unregistered_op_kind_fails_actionably():
    doc = export_artifact(_compile_block())
    mod = json.loads(json.dumps(doc))
    for t in mod["graph"]["tasks"]:
        t["spec"]["kind"] = "never-registered"
    mod["integrity"] = None
    with pytest.raises(ArtifactError, match="no registered|register_op"):
        import_artifact(mod)


def test_fusion_kernels_forward_compat_both_directions(tmp_path, monkeypatch):
    """Satellite: the v1.1 `fusion.kernels` field must interoperate both
    ways — a v1.0-era document (without it) imports cleanly under this
    reader, and a document from a *newer* minor (with the field plus
    future extras) warns-and-runs rather than failing."""
    from repro.core import lower
    from repro.kernels import register_all
    register_all()
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")   # tiny shapes: skip gate
    c = codo_opt(dm.gpt2_block(S=16, D=64), CodoOptions(budget_units=64),
                 cache=None)
    lower(c, jit=False)                          # record real routing
    doc = export_artifact(c)
    assert doc["schema_version"] == "1.5"
    assert len(doc["fusion"]["kernels"]) == len(doc["fusion"]["groups"])
    assert any(k.startswith("pallas:") for k in doc["fusion"]["kernels"])

    # direction 1: v1.0 document (no kernels field) -> imports, no warning
    old = json.loads(json.dumps(doc))
    del old["fusion"]["kernels"]
    old["schema_version"] = "1.0"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = import_artifact(old)
    assert not [x for x in w if issubclass(x.category, ArtifactWarning)]
    assert r.graph.structural_hash() == c.graph.structural_hash()

    # direction 2: a newer minor with the field plus an unknown fusion
    # extra -> warns (newer version, unknown field) and still runs
    newer = json.loads(json.dumps(doc))
    newer["schema_version"] = "1.7"
    newer["fusion"]["novel_fusion_field"] = True
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r2 = import_artifact(newer)
    msgs = [str(x.message) for x in w
            if issubclass(x.category, ArtifactWarning)]
    assert any("newer" in m for m in msgs)
    assert any("fusion.novel_fusion_field" in m for m in msgs)
    assert all(t.fn is not None for t in r2.graph.tasks)

    # routing drift warns (advisory field), never fails
    drift = json.loads(json.dumps(doc))
    drift["fusion"]["kernels"] = ["xla-fused"] * len(drift["fusion"]["groups"])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        import_artifact(drift)
    assert any("fusion.kernels drift" in str(x.message) for x in w
               if issubclass(x.category, ArtifactWarning))

    # ...but a misaligned kernels list is a hard validation error
    bad = json.loads(json.dumps(doc))
    bad["fusion"]["kernels"] = list(bad["fusion"]["kernels"]) + ["xla-fused"]
    with pytest.raises(ArtifactError, match="must align"):
        validate_artifact(bad)


def test_unknown_option_fields_warn_not_fail():
    """Forward compat reaches into `options`: a newer writer's extra knob
    is dropped with a warning, not a hard failure."""
    doc = export_artifact(_compile_block())
    mod = json.loads(json.dumps(doc))
    mod["options"]["novel_knob"] = 7
    mod["options"]["hw"]["novel_hw_field"] = 1.5
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = import_artifact(mod)
    msgs = [str(x.message) for x in w if issubclass(x.category, ArtifactWarning)]
    assert any("options.novel_knob" in m for m in msgs)
    assert any("options.hw.novel_hw_field" in m for m in msgs)
    assert r.options.budget_units == 64      # known fields still apply


def test_corrupted_section_values_fail_with_artifact_errors():
    doc = export_artifact(_compile_block())

    bad = json.loads(json.dumps(doc))
    bad["cost"]["final_cycles"] = "fast"
    with pytest.raises(ArtifactError, match="cost.final_cycles"):
        import_artifact(bad)

    bad = json.loads(json.dumps(doc))
    bad["integrity"]["structural_hash"] = 123
    with pytest.raises(ArtifactError, match="integrity.structural_hash"):
        import_artifact(bad)

    bad = json.loads(json.dumps(doc))
    bad["schedule"]["degrees"] = {"t": "many"}
    with pytest.raises(ArtifactError, match="schedule does not reconstruct"):
        import_artifact(bad)


def test_cost_drift_warns():
    doc = export_artifact(_compile_block())
    mod = json.loads(json.dumps(doc))
    mod["cost"]["final_cycles"] *= 2
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        import_artifact(mod)
    assert any("cost-model drift" in str(x.message) for x in w)


# --------------------------------------------------------------------------
# Cache JSON mirror
# --------------------------------------------------------------------------


def test_cache_json_mirror_is_importable(tmp_path):
    cache = CompileCache(disk_dir=tmp_path, json_mirror=True)
    c = codo_opt(dm.residual_block(1, 8, 12), CodoOptions(budget_units=64),
                 cache=cache)
    jsons = list(tmp_path.glob("*.json"))
    assert len(jsons) == 1 and cache.stats.json_mirrors == 1
    r = import_artifact(jsons[0])
    assert r.graph.structural_hash() == c.graph.structural_hash()
    assert all(t.fn is not None for t in r.graph.tasks)
    # mirror rides with the pickle lifecycle
    cache.clear(disk=True)
    assert not list(tmp_path.glob("*.json")) and not list(tmp_path.glob("*.pkl"))


def test_cache_mirror_ships_to_process_pool_workers(tmp_path):
    from repro.core.compiler import ablation_jobs, batch_workloads, codo_opt_batch
    wl = batch_workloads(seq=8)
    jobs = ablation_jobs({"gpt2-medium": wl["gpt2-medium"]},
                         presets=["opt2", "opt5"], budget_units=64)
    cache = CompileCache(disk_dir=tmp_path, json_mirror=True)
    res = codo_opt_batch(jobs, cache=cache, max_workers=2, executor="process")
    assert all(r.ok for r in res)
    jsons = list(tmp_path.glob("*.json"))
    assert jsons, "workers must honour the parent's json_mirror flag"
    assert import_artifact(jsons[0]).graph.name


def test_cache_mirror_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("CODO_CACHE_JSON", "1")
    cache = CompileCache(disk_dir=tmp_path)
    assert cache.json_mirror
    monkeypatch.delenv("CODO_CACHE_JSON")
    assert not CompileCache(disk_dir=tmp_path).json_mirror


# --------------------------------------------------------------------------
# CLI verbs + profile
# --------------------------------------------------------------------------


def test_cli_export_import_profile(tmp_path, capsys):
    art_dir = tmp_path / "arts"
    rc = compiler_main(["--configs", "gpt2-medium", "--opts", "opt5",
                        "--executor", "thread", "--jobs", "1", "--no-cache",
                        "--seq", "8", "--export", str(art_dir), "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "exported 1/1 artifacts" in out
    assert "pass profile" in out and "schedule" in out
    path = art_dir / "gpt2-medium-opt5.json"
    assert path.exists()

    rc = compiler_main(["--import-artifact", str(path), "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "artifact gpt2_medium (schema v1.5)" in out
    assert "== codo_opt(gpt2_medium) ==" in out
    assert "-- passes(gpt2_medium) --" in out

    assert "gpt2_medium" in artifact_summary(path)


def test_profile_table_skips_cache_hits():
    cache = CompileCache()
    a = codo_opt(dm.residual_block(1, 8, 12), CodoOptions(budget_units=64),
                 cache=cache)
    b = codo_opt(dm.residual_block(1, 8, 12), CodoOptions(budget_units=64),
                 cache=cache)
    assert b.cache_hit
    table = profile_table([a.diagnostics, b.diagnostics])
    assert "1 compiles" in table
    assert profile_table([b.diagnostics]).startswith("profile: no pass records")


def test_serve_artifact_mode(tmp_path):
    path = tmp_path / "d.json"
    export_artifact(_compile_block(), path)
    proc = _fresh_interpreter(f"""
        import repro.launch.serve as serve
        rc = serve.main(["--artifact", {str(path)!r}, "--requests", "2"])
        assert rc == 0
    """)
    assert proc.returncode == 0, proc.stderr
    assert "requests in" in proc.stdout


def test_lowered_artifact_matches_direct_lowering():
    c = _compile_block()
    direct = lower(c, jit=False)
    via_artifact = lower(import_artifact(export_artifact(c)), jit=False)
    env = dm.random_inputs(dm.residual_block(1, 8, 12))
    got, want = via_artifact(env), direct(env)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-6)
    assert [g.tasks for g in via_artifact.groups] == \
        [g.tasks for g in direct.groups]


# --------------------------------------------------------------------------
# Bound-weight payloads (schema v1.3): self-contained served models
# --------------------------------------------------------------------------


def _bound_mlp():
    """A tiny compiled MLP with every weight bound to 1.5× the
    deterministic initializer — outputs observably differ from what an
    initializer fallback would produce."""
    import repro.api as codo
    from repro.core import frontend

    def mlp(x):
        h = frontend.fc(x, 8, relu=True)
        return frontend.fc(h, 4)

    p = codo.compile(mlp, (4, 6), cache=None)
    p.bind(**{b.name: np.float32(1.5)
              * frontend.weight_init(b.shape, b.dtype)
              for b in p.graph.weights()})
    return p


def test_v13_weights_roundtrip_embedded_and_sidecar(tmp_path):
    from repro.core.artifact import artifact_weights, sidecar_path
    p = _bound_mlp()
    want = dict(p._bindings)
    assert want                                     # the test is non-vacuous

    emb = tmp_path / "emb.json"
    p.export(str(emb), weights=True)
    doc = json.loads(emb.read_text())
    assert doc["schema_version"] == "1.5"
    assert doc["weights"]["format"] == "embedded"
    got = artifact_weights(emb)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], np.asarray(want[k]))

    sc = tmp_path / "sc.json"
    p.export(str(sc), weights=True, sidecar=True)
    assert sidecar_path(sc).exists()
    doc = json.loads(sc.read_text())
    assert doc["weights"]["format"] == "sidecar"
    assert doc["weights"]["file"] == sidecar_path(sc).name
    assert all("data" not in e for e in doc["weights"]["arrays"].values())
    got = artifact_weights(sc)
    for k in want:
        np.testing.assert_array_equal(got[k], np.asarray(want[k]))


def test_v13_fresh_interpreter_serves_without_weight_init(tmp_path):
    """The self-contained-model property: a weight-carrying artifact
    executes in a fresh interpreter with ``weight_init`` unreachable —
    no model code, no initializer, bit-identical outputs."""
    import repro.api as codo
    p = _bound_mlp()
    x = np.random.default_rng(0).standard_normal((4, 6)).astype("float32")
    np.savez(tmp_path / "ref.npz", x=x, y=np.asarray(p(x)))
    path = tmp_path / "m.json"
    p.export(str(path), weights=True)
    del codo

    proc = _fresh_interpreter(f"""
        import numpy as np
        from repro.core import frontend

        def boom(shape, dtype=np.float32):
            raise AssertionError("weight_init reached while serving a "
                                 "v1.3 weight-carrying artifact")
        frontend.weight_init = boom

        import repro.api as codo
        p = codo.load({str(path)!r})
        ref = np.load({str(tmp_path / "ref.npz")!r})
        out = np.asarray(p(ref["x"]))
        np.testing.assert_array_equal(out, ref["y"])
        print("V13_SELF_CONTAINED_OK")
    """)
    assert proc.returncode == 0, proc.stderr
    assert "V13_SELF_CONTAINED_OK" in proc.stdout


def test_v13_hash_mismatch_fails(tmp_path):
    from repro.core.artifact import artifact_weights
    p = _bound_mlp()
    doc = p.export(weights=True)
    name = next(iter(doc["weights"]["arrays"]))

    forged = json.loads(json.dumps(doc))
    forged["weights"]["arrays"][name]["sha256"] = "0" * 64
    with pytest.raises(ArtifactError, match="content hash mismatch"):
        artifact_weights(forged)

    import base64
    tampered = json.loads(json.dumps(doc))
    entry = tampered["weights"]["arrays"][name]
    raw = bytearray(base64.b64decode(entry["data"]))
    raw[0] ^= 0xFF                                  # flip payload bits
    entry["data"] = base64.b64encode(bytes(raw)).decode()
    with pytest.raises(ArtifactError, match="content hash mismatch"):
        artifact_weights(tampered)


def test_v13_missing_sidecar_fails(tmp_path):
    import repro.api as codo
    from repro.core.artifact import artifact_weights, sidecar_path
    p = _bound_mlp()
    path = tmp_path / "m.json"
    p.export(str(path), weights=True, sidecar=True)
    sidecar_path(path).unlink()
    with pytest.raises(ArtifactError, match="missing or unreadable"):
        artifact_weights(path)
    with pytest.raises(ArtifactError, match="missing or unreadable"):
        codo.load(path)                             # load never half-binds


def test_v13_validation_rejects_malformed_weights():
    doc = _bound_mlp().export(weights=True)
    name = next(iter(doc["weights"]["arrays"]))

    bad_fmt = json.loads(json.dumps(doc))
    bad_fmt["weights"]["format"] = "carrier-pigeon"
    with pytest.raises(ArtifactError, match="weights.format"):
        validate_artifact(bad_fmt)

    no_file = json.loads(json.dumps(doc))
    no_file["weights"]["format"] = "sidecar"
    with pytest.raises(ArtifactError, match="required for sidecar"):
        validate_artifact(no_file)

    no_data = json.loads(json.dumps(doc))
    del no_data["weights"]["arrays"][name]["data"]
    with pytest.raises(ArtifactError, match="required for embedded"):
        validate_artifact(no_data)

    not_weight = json.loads(json.dumps(doc))
    arrays = not_weight["weights"]["arrays"]
    arrays["x"] = dict(arrays[name])                # an *input* buffer
    with pytest.raises(ArtifactError, match="not a weight buffer"):
        validate_artifact(not_weight)


def test_pre_v13_documents_without_weights_still_import():
    from repro.core.artifact import artifact_weights
    doc = export_artifact(_compile_block())         # no weights section
    assert "weights" not in doc
    assert artifact_weights(doc) == {}
    c = import_artifact(json.loads(json.dumps(doc, sort_keys=True)))
    assert c.graph.structural_hash() == doc["integrity"]["structural_hash"]
