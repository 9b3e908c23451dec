"""PassManager / compile-cache / batch-driver subsystem tests.

Covers the tentpole invariants: pipeline ordering + invalidation re-runs,
ablation presets as data, content-addressed cache hits that skip the
pipeline (asserted via pass counters), disk-cache round trips, and the
batch ablation driver + CLI.
"""

import dataclasses

import pytest

from repro.core import (ABLATION_PRESETS, CodoOptions, CompileCache,
                        PASS_RUN_COUNTS, Pass, PassManager, codo_opt,
                        codo_opt_batch, verify_violation_free)
from repro.core.compiler import ablation_jobs, main as compiler_main
from repro.models import dataflow_models as dm


def small_graph():
    return dm.residual_block(1, 8, 12)


# --------------------------------------------------------------------------
# Pipeline ordering + presets
# --------------------------------------------------------------------------


def test_default_pipeline_order_matches_paper():
    assert PassManager.default().names() == [
        "coarse", "fine", "reuse", "buffers", "offchip", "schedule"]


def test_presets_are_pass_sets():
    for name, names in ABLATION_PRESETS.items():
        opts = CodoOptions.preset(name)
        assert opts.pass_set() == names, name
    # legacy constructors are the same data
    assert CodoOptions.opt3().pass_set() == ABLATION_PRESETS["opt3"]
    assert CodoOptions.opt5().pass_set() == ABLATION_PRESETS["opt5"]
    with pytest.raises(KeyError):
        CodoOptions.preset("opt9")
    with pytest.raises(KeyError):
        CodoOptions.from_passes({"coarse", "nonexistent"})


def test_from_passes_rejects_inexpressible_sets():
    # reuse/offchip are gated together: one without the other must raise,
    # not silently widen the pass set
    with pytest.raises(ValueError):
        CodoOptions.from_passes({"coarse", "offchip", "buffers"})
    with pytest.raises(ValueError):
        CodoOptions.from_passes({"reuse", "buffers"})


def test_census_can_be_disabled():
    mgr = PassManager(census=False)
    c = codo_opt(small_graph(), cache=None, manager=mgr)
    assert all(r.coarse_before == -1 for r in c.diagnostics.records)
    assert "ms" in c.diagnostics.table()
    assert not verify_violation_free(c)


def test_preset_overrides_forwarded():
    opts = CodoOptions.preset("opt5", budget_units=128, hbm_channels=4)
    assert opts.budget_units == 128 and opts.hbm_channels == 4


def test_diagnostics_record_passes_and_invalidation_rerun():
    c = codo_opt(small_graph(), cache=None)
    names = [(r.name, r.rerun) for r in c.diagnostics.records]
    # reuse declares it invalidates fine -> fine re-runs right after, merged
    assert names == [("coarse", False), ("fine", False), ("reuse", False),
                     ("fine", True), ("buffers", False), ("offchip", False),
                     ("schedule", False)]
    assert all(r.coarse_after == 0 for r in c.diagnostics.records[1:])
    assert c.diagnostics.total_seconds > 0
    assert "fine" in c.diagnostics.pass_seconds


def test_disabled_passes_do_not_run():
    c = codo_opt(small_graph(), CodoOptions.preset("opt2"), cache=None)
    assert c.diagnostics.pass_names == ["coarse", "buffers"]
    assert c.fine_report is None and c.schedule_report is None


def test_register_before_after_ordering():
    mgr = PassManager.default()
    noop = Pass(name="noop", run=lambda g, o, out: None)
    mgr.register(noop, before="buffers")
    assert mgr.names().index("noop") == mgr.names().index("buffers") - 1
    with pytest.raises(ValueError):
        mgr.register(noop)
    c = codo_opt(small_graph(), cache=None, manager=mgr)
    assert "noop" in c.diagnostics.pass_names
    assert not verify_violation_free(c)


# --------------------------------------------------------------------------
# Structural hashing
# --------------------------------------------------------------------------


def test_structural_hash_stable_across_builds():
    assert small_graph().structural_hash() == small_graph().structural_hash()


def test_structural_hash_sensitive_to_structure():
    g1, g2 = small_graph(), small_graph()
    g2.tasks[0].loops[0].trip += 1
    assert g1.structural_hash() != g2.structural_hash()


def test_structural_hash_sees_semantic_constants():
    # scale factors are OpSpec attrs — structural data — so graphs with
    # different numerics never collide in the cache
    from repro.models.dataflow_models import GB

    def build(s):
        b = GB("g")
        x = b.load(b.input("x", (4, 4)))
        b.mark_output(b.scale(x, s))
        return b.g

    assert build(0.5).structural_hash() != build(0.25).structural_hash()
    assert build(0.5).structural_hash() == build(0.5).structural_hash()


def test_structural_hash_sees_closure_const_tags():
    # closure-built tasks keep the legacy contract: constants surface via
    # const: tags (specs are absent, so tags are the only structural trace)
    from repro.core import DataflowGraph, ewise_task

    def build(s):
        g = DataflowGraph("g")
        g.buffer("x", (4,), kind="input")
        g.buffer("o", (4,), kind="output")
        t = ewise_task("t", "o", ["x"], (4,), fn=lambda e, _s=s: {"o": e["x"] * _s})
        t.tags.add(f"const:scale:{s!r}")
        g.add_task(t)
        return g

    assert build(0.5).structural_hash() != build(0.25).structural_hash()


def test_options_cache_key_sensitive():
    assert CodoOptions().cache_key() == CodoOptions().cache_key()
    assert CodoOptions().cache_key() != CodoOptions(budget_units=64).cache_key()
    assert CodoOptions.opt4().cache_key() != CodoOptions.opt5().cache_key()


# --------------------------------------------------------------------------
# Compile cache
# --------------------------------------------------------------------------


def test_cache_hit_skips_passes_and_preserves_result():
    cache = CompileCache()
    c1 = codo_opt(small_graph(), cache=cache)
    counts_after_first = dict(PASS_RUN_COUNTS)
    # fresh build of the same model -> same structural hash -> hit
    c2 = codo_opt(small_graph(), cache=cache)
    assert dict(PASS_RUN_COUNTS) == counts_after_first, "cache hit re-ran passes"
    assert c2.cache_hit and not c1.cache_hit
    assert c2.speedup == c1.speedup
    assert c2.fifo_fraction == c1.fifo_fraction
    assert c2.final.total_cycles == c1.final.total_cycles
    assert c2.compile_seconds < c1.compile_seconds
    assert not verify_violation_free(c2)
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_cache_respects_options():
    cache = CompileCache()
    codo_opt(small_graph(), CodoOptions.opt2(), cache=cache)
    c = codo_opt(small_graph(), CodoOptions.opt5(), cache=cache)
    assert not c.cache_hit
    assert cache.stats.misses == 2


def test_cache_returns_isolated_graphs():
    cache = CompileCache()
    c1 = codo_opt(small_graph(), cache=cache)
    c1.graph.tasks[0].loops[0].parallel = 12345   # caller mutates result
    c2 = codo_opt(small_graph(), cache=cache)
    assert c2.cache_hit
    assert c2.graph.tasks[0].loops[0].parallel != 12345


def test_cache_lru_eviction():
    cache = CompileCache(maxsize=1)
    codo_opt(dm.gesummv(24), cache=cache)
    codo_opt(dm.atax(16, 16), cache=cache)      # evicts gesummv
    assert cache.stats.evictions == 1
    c = codo_opt(dm.gesummv(24), cache=cache)
    assert not c.cache_hit


def test_disk_cache_cross_instance_is_executable(tmp_path):
    d = tmp_path / "cc"
    c1 = codo_opt(small_graph(), cache=CompileCache(disk_dir=d))
    assert list(d.glob("*.pkl")), "no disk entry written"
    # a fresh cache (fresh process analogue) hits via the pickle tier
    cache2 = CompileCache(disk_dir=d)
    counts = dict(PASS_RUN_COUNTS)
    c2 = codo_opt(small_graph(), cache=cache2)
    assert dict(PASS_RUN_COUNTS) == counts
    assert c2.cache_hit and cache2.stats.disk_hits == 1
    assert c2.speedup == c1.speedup
    # declarative disk entries reload fully executable: every task re-derives
    # its fn from its OpSpec, and the lowered program matches the oracle
    assert all(t.fn is not None for t in c2.graph.tasks)
    assert all(not t.fn_is_closure for t in c2.graph.tasks)
    assert not verify_violation_free(c2)
    from repro.core import verify_lowering
    src = small_graph()
    verify_lowering(src, c2, dm.random_inputs(src), rtol=3e-4, atol=3e-4)
    # executable entries are promoted into the memory tier
    c3 = codo_opt(small_graph(), cache=cache2)
    assert c3.cache_hit
    assert cache2.stats.promotions == 1 and cache2.stats.hits == 1
    assert len(cache2) == 1


def test_closure_disk_entry_stripped_and_raises_on_lower(tmp_path):
    # closure-built graphs keep the old behavior: disk entries are
    # structural-only, lowering raises a clear error, and they are NOT
    # promoted into the memory tier
    from repro.core import DataflowGraph, ewise_task, lower
    from repro.core.graph import GraphError

    def build():
        g = DataflowGraph("closure_g")
        g.buffer("x", (8,), kind="input")
        g.buffer("o", (8,), kind="output")
        g.add_task(ewise_task("t", "o", ["x"], (8,),
                              fn=lambda e: {"o": e["x"] * 2}))
        return g

    d = tmp_path / "cc"
    codo_opt(build(), cache=CompileCache(disk_dir=d))
    cache2 = CompileCache(disk_dir=d)
    c = codo_opt(build(), cache=cache2)
    assert c.cache_hit
    assert all(t.fn is None for t in c.graph.tasks)
    with pytest.raises(GraphError, match="no numeric"):
        lower(c)
    assert cache2.stats.promotions == 0 and len(cache2) == 0


def test_cache_returns_isolated_buffer_plans():
    from repro.core import downgrade_to_pingpong
    cache = CompileCache()
    c1 = codo_opt(small_graph(), cache=cache)
    fifo_buf = next(b for b, v in c1.buffer_plan.impl.items() if v == "fifo")
    downgrade_to_pingpong(c1.graph, c1.buffer_plan, fifo_buf, "test mutation")
    c2 = codo_opt(small_graph(), cache=cache)
    assert c2.cache_hit
    assert c2.buffer_plan.impl[fifo_buf] == "fifo", \
        "post-compile plan mutation leaked into the cache"


def test_corrupt_disk_entry_degrades_to_recompile(tmp_path):
    d = tmp_path / "cc"
    cache = CompileCache(disk_dir=d)
    codo_opt(small_graph(), cache=cache)
    for p in d.glob("*.pkl"):
        p.write_bytes(b"not a pickle")
    cache2 = CompileCache(disk_dir=d)
    c = codo_opt(small_graph(), cache=cache2)
    assert not c.cache_hit and cache2.stats.disk_errors == 1


# --------------------------------------------------------------------------
# Batch driver + CLI
# --------------------------------------------------------------------------


def test_batch_driver_grid_and_cache():
    workloads = {"gesummv": lambda: dm.gesummv(24),
                 "residual_block": lambda: dm.residual_block(1, 8, 12)}
    cache = CompileCache()
    jobs = ablation_jobs(workloads, presets=["opt1", "opt5"], budget_units=64)
    results = codo_opt_batch(jobs, cache=cache, max_workers=4)
    assert len(results) == 4
    assert all(r.ok for r in results), [r.error for r in results]
    by_cell = {(r.config, r.preset): r.compiled for r in results}
    assert by_cell[("residual_block", "opt5")].speedup > \
        by_cell[("residual_block", "opt1")].speedup
    # identical second batch: every cell served from cache
    again = codo_opt_batch(jobs, cache=cache, max_workers=4)
    assert all(r.cache_hit for r in again)
    # full-pipeline cells stay violation-free even when served from cache
    # (opt1 keeps coarse violations by design — the Fig. 10 lesson)
    assert all(not verify_violation_free(r.compiled)
               for r in again if r.preset == "opt5")


def test_batch_driver_process_pool(tmp_path):
    """The Table VII grid fans out over worker processes: jobs pickle,
    results come back executable, and a second grid is served from the
    shared disk tier."""
    from repro.core.compiler import batch_workloads

    wl = batch_workloads(seq=8)
    sub = {k: wl[k] for k in ("gpt2-medium", "mamba2-780m")}
    jobs = ablation_jobs(sub, presets=["opt1", "opt5"], budget_units=64)
    cache = CompileCache(disk_dir=tmp_path / "cc")
    results = codo_opt_batch(jobs, cache=cache, max_workers=2,
                             executor="process")
    assert len(results) == 4 and all(r.ok for r in results), \
        [r.error for r in results]
    assert not any(r.cache_hit for r in results)
    # results crossed a process boundary and are still executable
    assert all(t.fn is not None
               for r in results for t in r.compiled.graph.tasks)
    again = codo_opt_batch(jobs, cache=CompileCache(disk_dir=tmp_path / "cc"),
                           max_workers=2, executor="process")
    assert all(r.cache_hit for r in again)


def test_batch_process_pool_rejects_unpicklable_jobs():
    jobs = ablation_jobs({"gesummv": lambda: dm.gesummv(24)},
                         presets=["opt5"], budget_units=64)
    jobs = jobs * 2  # need >1 job to engage the pool
    with pytest.raises(ValueError, match="picklable"):
        codo_opt_batch(jobs, cache=None, max_workers=2, executor="process")


def test_lower_memoization_structural():
    from repro.core import LOWER_CACHE_STATS, clear_lower_cache, lower

    clear_lower_cache()
    c1 = codo_opt(small_graph(), cache=None)
    p1 = lower(c1, jit=False)
    # structurally identical fresh compile reuses the built program
    c2 = codo_opt(small_graph(), cache=None)
    p2 = lower(c2, jit=False)
    assert LOWER_CACHE_STATS["hits"] == 1
    assert p2.fn is p1.fn
    # the hit mirrors fusion decisions onto the caller's graph
    assert [t.fused_group for t in c2.graph.tasks] == \
        [t.fused_group for t in c1.graph.tasks]
    env = dm.random_inputs(small_graph())
    import numpy as np
    for k, v in p1(env).items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(p2(env)[k]))


def test_batch_driver_reports_cell_errors():
    def boom():
        raise RuntimeError("bad build")
    results = codo_opt_batch(
        ablation_jobs({"boom": boom}, presets=["opt5"]), cache=None)
    assert len(results) == 1 and not results[0].ok
    assert "bad build" in results[0].error


def test_arch_block_graphs_compile_violation_free():
    from repro.configs import get_config
    from repro.models.dataflow_models import arch_block_graph
    # one config per family: dense / moe / ssm / hybrid / enc-dec
    for name in ("gpt2-medium", "mixtral-8x22b", "mamba2-780m",
                 "recurrentgemma-9b", "whisper-large-v3"):
        g = arch_block_graph(get_config(name), S=16)
        g.validate()
        c = codo_opt(g, CodoOptions(budget_units=64), cache=None)
        assert not verify_violation_free(c), name
        assert c.speedup >= 1.0, name


def test_cli_smoke_and_second_run_hits_cache(tmp_path, capsys):
    argv = ["--configs", "gpt2-medium", "--opts", "opt1,opt2", "--seq", "8",
            "--budget", "64", "--cache-dir", str(tmp_path / "cc"),
            "--csv", str(tmp_path / "grid.csv"), "--jobs", "2"]
    assert compiler_main(argv) == 0
    out1 = capsys.readouterr().out
    assert "gpt2-medium" in out1 and "0 cache hits" in out1
    assert (tmp_path / "grid.csv").read_text().count("gpt2-medium") == 2
    assert compiler_main(argv) == 0
    out2 = capsys.readouterr().out
    assert "2 cache hits" in out2


def test_cli_list_and_bad_config(capsys):
    assert compiler_main(["--list"]) == 0
    out = capsys.readouterr().out.split()
    assert len(out) == 13 and "resnet18" in out and "gpt2-medium" in out \
        and "gpt2_block" in out
    with pytest.raises(SystemExit):
        compiler_main(["--configs", "not-a-config"])


def test_compiled_dataflow_report_mentions_diagnostics():
    c = codo_opt(small_graph(), cache=None)
    rep = c.report()
    assert "diagnostics:" in rep and "compile time" in rep
    assert "cache hit" in dataclasses.replace(
        c.diagnostics, cache_hit=True).table()


def test_batch_pool_never_forks_a_device_holder(monkeypatch):
    """The parent resolves the backend once: fork only where it holds no
    accelerator, and every worker gets the backend pinned so it never
    asks jax (and so never initializes a device) itself."""
    import os

    from repro.core import compiler
    monkeypatch.delenv("CODO_MP_START", raising=False)
    assert compiler._mp_context("tpu").get_start_method() == "spawn"
    if "fork" in compiler.multiprocessing.get_all_start_methods():
        assert compiler._mp_context("cpu").get_start_method() == "fork"
    monkeypatch.setenv("CODO_BACKEND", "")
    compiler._init_batch_worker(None, False, backend="tpu")
    assert os.environ["CODO_BACKEND"] == "tpu"
