"""Serving-runtime battery (ISSUE 8): dynamic-batching window semantics,
cache-accounted single batched compile, bit-identical batched numerics,
queue-full backpressure, zero-downtime hot-swap under in-flight load,
worker-crash respawn/retry, and the deprecation shims.

Synchronization policy: every wait in here is event-based —
``pause``/``resume``/``flush``/``ServeFuture.result(timeout)`` — never a
sleep.  ``pause()`` + N ``submit()`` + ``resume()`` is the deterministic
way to place N requests inside one batching window.
"""

from __future__ import annotations

import importlib
import os
import pathlib
import warnings

import numpy as np
import pytest

import repro.api as codo
from repro.core.cache import CompileCache
from repro.kernels import register_all
from repro.serving import (QueueFullError, ServeConfig, ServeError,
                           ServingRuntime)

register_all()

TIMEOUT = 300        # generous per-future bound; waits are event-based


def _model(x):
    h = codo.F.fc(x, 24, relu=True)
    return codo.F.fc(h, 12)


def _bound_program(cache, scale=1.0, shape=(8, 16)):
    """A compiled tiny MLP with deterministic bound weights (``scale``
    makes two observably different model generations)."""
    p = codo.compile(_model, shape, cache=cache)
    w = {b.name: scale * np.asarray(
        codo.F.weight_init(b.shape, b.dtype)) for b in p.graph.weights()}
    p.bind(**w)
    return p


def _inputs(n, shape=(8, 16), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(shape).astype("float32") for _ in range(n)]


# --------------------------------------------------------------------------
# Dynamic batching
# --------------------------------------------------------------------------


def test_window_coalesces_to_exactly_one_batched_compile():
    cache = CompileCache()
    p = _bound_program(cache)
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=4),
                        cache=cache) as rt:
        rt.add_model("m", p)
        xs = _inputs(4)

        misses0 = cache.stats.misses
        rt.pause()
        futs = [rt.submit("m", x=x) for x in xs]
        rt.resume()
        outs = [f.result(timeout=TIMEOUT) for f in futs]
        assert len(outs) == 4
        # One dispatch group, all four coalesced, ONE compile for the
        # leading-batch-dim design (cache accounting: exactly one miss).
        assert rt.stats.batches == 1
        assert rt.stats.batched_requests == 4
        assert rt.stats.fallback_requests == 0
        assert cache.stats.misses - misses0 == 1

        # A second identical window re-uses the batched program: zero new
        # compiles anywhere.
        misses1 = cache.stats.misses
        rt.pause()
        futs = [rt.submit("m", x=x) for x in xs]
        rt.resume()
        [f.result(timeout=TIMEOUT) for f in futs]
        assert rt.stats.batches == 2
        assert cache.stats.misses == misses1


def test_batched_results_bit_identical_to_sequential():
    cache = CompileCache()
    p = _bound_program(cache)
    name = p.output_names[0]
    xs = _inputs(6, seed=3)
    want = [np.asarray(p(x)) for x in xs]
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=6),
                        cache=cache) as rt:
        rt.add_model("m", p)
        rt.pause()
        futs = [rt.submit("m", x=x) for x in xs]
        rt.resume()
        outs = [f.result(timeout=TIMEOUT) for f in futs]
    assert rt.stats.batched_requests == 6
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(got[name], ref)   # bit-identical


def test_mixed_shape_traffic_never_cross_batches():
    cache = CompileCache()
    p_a = _bound_program(cache, shape=(8, 16))
    p_b = _bound_program(cache, shape=(4, 16))
    name = p_a.output_names[0]
    xa, xb = _inputs(2, (8, 16), seed=1), _inputs(2, (4, 16), seed=2)
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=8),
                        cache=cache) as rt:
        rt.add_model("a", p_a)
        rt.add_model("b", p_b)
        rt.pause()
        futs = [rt.submit("a", x=xa[0]), rt.submit("b", x=xb[0]),
                rt.submit("a", x=xa[1]), rt.submit("b", x=xb[1])]
        rt.resume()
        outs = [f.result(timeout=TIMEOUT) for f in futs]
    # Two dispatch groups — one per model — and every batched program
    # that exists has each model's own shape (no cross-batching).
    assert rt.stats.batches == 2
    for handle, prog in ((rt._models["a"], p_a), (rt._models["b"], p_b)):
        for size, bp in handle.batched.items():
            batched_in = bp.graph.buffers[prog.input_names[0]]
            orig_in = prog.graph.buffers[prog.input_names[0]]
            assert tuple(batched_in.shape) == (size, *orig_in.shape)
    np.testing.assert_array_equal(outs[0][name], np.asarray(p_a(xa[0])))
    np.testing.assert_array_equal(outs[1][name], np.asarray(p_b(xb[0])))
    np.testing.assert_array_equal(outs[2][name], np.asarray(p_a(xa[1])))
    np.testing.assert_array_equal(outs[3][name], np.asarray(p_b(xb[1])))


def test_non_batchable_design_falls_back_per_request():
    from repro.models import dataflow_models as dm
    cache = CompileCache()
    g = dm.residual_block(1, 8, 12)         # conv ops: not batchable
    p = codo.compile(g, cache=cache)
    from repro.core.frontend import batch_blockers
    assert batch_blockers(p.source)         # precondition of this test
    env = dm.random_inputs(g, seed=0)
    want = p.lower(jit=True)(p.make_env(**env))
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=3),
                        cache=cache) as rt:
        rt.add_model("m", p, warm=False)
        rt.pause()
        futs = [rt.submit("m", **env) for _ in range(3)]
        rt.resume()
        outs = [f.result(timeout=TIMEOUT) for f in futs]
    assert rt.stats.fallback_requests == 3
    assert rt.stats.batched_requests == 0
    for out in outs:
        for k in want:
            np.testing.assert_array_equal(out[k], np.asarray(want[k]))


# --------------------------------------------------------------------------
# Backpressure + request-path errors
# --------------------------------------------------------------------------


def test_bounded_queue_backpressure():
    cache = CompileCache()
    p = _bound_program(cache)
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=4,
                                    max_queue=4), cache=cache) as rt:
        rt.add_model("m", p)
        rt.pause()                          # queue fills deterministically
        xs = _inputs(4)
        futs = [rt.submit("m", x=x) for x in xs]
        with pytest.raises(QueueFullError):
            rt.submit("m", x=xs[0])
        rt.resume()
        assert all(f.result(timeout=TIMEOUT) is not None for f in futs)
    assert rt.stats.completed == 4


def test_unknown_model_and_closed_runtime_raise():
    cache = CompileCache()
    p = _bound_program(cache)
    rt = ServingRuntime(ServeConfig(batch_window_ms=1), cache=cache)
    rt.add_model("m", p)
    with pytest.raises(KeyError):
        rt.submit("nope", x=_inputs(1)[0])
    rt.close()
    with pytest.raises(ServeError):
        rt.submit("m", x=_inputs(1)[0])


def test_execution_error_is_a_clean_response():
    cache = CompileCache()
    p = _bound_program(cache)
    with ServingRuntime(ServeConfig(batch_window_ms=1), cache=cache) as rt:
        rt.add_model("m", p)
        fut = rt.submit("m", wrong_name=_inputs(1)[0])
        with pytest.raises(ServeError, match="execution failed"):
            fut.result(timeout=TIMEOUT)
    assert rt.stats.failed == 1


# --------------------------------------------------------------------------
# Hot-swap
# --------------------------------------------------------------------------


def test_hot_swap_under_load_loses_zero_requests():
    cache = CompileCache()
    p_old = _bound_program(cache, scale=1.0)
    p_new = _bound_program(cache, scale=2.0)
    name = p_old.output_names[0]
    xs = _inputs(12, seed=7)
    want_old = [np.asarray(p_old(x)) for x in xs]
    want_new = [np.asarray(p_new(x)) for x in xs]
    with ServingRuntime(ServeConfig(batch_window_ms=500, max_batch=4),
                        cache=cache) as rt:
        rt.add_model("m", p_old)
        rt.pause()
        futs = [rt.submit("m", x=x) for x in xs]    # 3 windows queued
        rt.resume()
        # Swap while those requests are in flight/queued: the replacement
        # is warmed before the atomic flip; dispatched work drains on the
        # old design.
        rt.swap("m", p_new)
        outs = [f.result(timeout=TIMEOUT) for f in futs]
    assert rt.stats.swaps == 1
    assert rt.stats.completed == len(xs)            # zero requests lost
    assert rt.stats.failed == 0
    from_old = from_new = 0
    for got, old, new in zip(outs, want_old, want_new):
        # Every response is *exactly* one generation's answer — a swap
        # mid-stream never yields a mixed or torn result.
        if np.array_equal(got[name], old):
            from_old += 1
        elif np.array_equal(got[name], new):
            from_new += 1
        else:
            raise AssertionError("response matches neither generation")
    assert from_old + from_new == len(xs)


def test_post_swap_requests_serve_the_new_design():
    cache = CompileCache()
    p_old = _bound_program(cache, scale=1.0)
    p_new = _bound_program(cache, scale=3.0)
    name = p_old.output_names[0]
    x = _inputs(1, seed=9)[0]
    with ServingRuntime(ServeConfig(batch_window_ms=1), cache=cache) as rt:
        rt.add_model("m", p_old)
        np.testing.assert_array_equal(
            rt.submit("m", x=x).result(timeout=TIMEOUT)[name],
            np.asarray(p_old(x)))
        rt.swap("m", p_new)
        np.testing.assert_array_equal(
            rt.submit("m", x=x).result(timeout=TIMEOUT)[name],
            np.asarray(p_new(x)))
    assert rt.stats.failed == 0


def test_swap_unknown_model_raises():
    cache = CompileCache()
    with ServingRuntime(ServeConfig(batch_window_ms=1), cache=cache) as rt:
        with pytest.raises(KeyError):
            rt.swap("ghost", _bound_program(cache))


# --------------------------------------------------------------------------
# Process workers: shared disk cache, crash respawn, bounded retries
# --------------------------------------------------------------------------


def _export_served(tmp_path, cache, scale=1.0):
    p = _bound_program(cache, scale=scale)
    path = tmp_path / f"served_{scale}.json"
    p.export(str(path), weights=True)       # self-contained v1.3 artifact
    return p, str(path)


def test_worker_pool_serves_batched_and_shares_disk_cache(tmp_path):
    cache = CompileCache(disk_dir=tmp_path / "cache")
    p, path = _export_served(tmp_path, cache)
    name = p.output_names[0]
    xs = _inputs(4, seed=11)
    want = [np.asarray(p(x)) for x in xs]
    before = set((tmp_path / "cache").glob("*.pkl"))
    with ServingRuntime(ServeConfig(batch_window_ms=200, max_batch=4,
                                    workers=1), cache=cache) as rt:
        rt.add_model("m", path)
        rt.pause()
        futs = [rt.submit("m", x=x) for x in xs]
        rt.resume()
        outs = [f.result(timeout=TIMEOUT) for f in futs]
    assert rt.stats.batched_requests == 4
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(got[name], ref)
    # The worker compiled the batched design through the *shared* disk
    # cache: the parent's cache dir gained entries it can now hit.
    assert set((tmp_path / "cache").glob("*.pkl")) > before


def test_worker_crash_respawns_and_retries_request(tmp_path, monkeypatch):
    marker = tmp_path / "crash.marker"
    marker.write_text("armed")
    monkeypatch.setenv("CODO_SERVE_FAULT", f"crash_once:{marker}")
    cache = CompileCache(disk_dir=tmp_path / "cache")
    p, path = _export_served(tmp_path, cache)
    name = p.output_names[0]
    x = _inputs(1, seed=13)[0]
    with ServingRuntime(ServeConfig(batch_window_ms=1, workers=1,
                                    max_retries=2), cache=cache) as rt:
        rt.add_model("m", path)
        fut = rt.submit("m", x=x)
        out = fut.result(timeout=TIMEOUT)   # survives the crash
    np.testing.assert_array_equal(out[name], np.asarray(p(x)))
    assert not marker.exists()              # the fault actually fired
    assert rt.stats.respawns >= 1           # pool was rebuilt
    assert rt.stats.retries >= 1            # the request was re-queued
    assert rt.stats.completed == 1


def test_worker_crash_bounded_retries_then_clean_error(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("CODO_SERVE_FAULT", "crash")    # dies every time
    cache = CompileCache(disk_dir=tmp_path / "cache")
    _p, path = _export_served(tmp_path, cache)
    x = _inputs(1, seed=17)[0]
    rt = ServingRuntime(ServeConfig(batch_window_ms=1, workers=1,
                                    max_retries=1), cache=cache)
    try:
        rt.add_model("m", path)
        fut = rt.submit("m", x=x)
        with pytest.raises(ServeError, match="worker crashes"):
            fut.result(timeout=TIMEOUT)
        assert rt.stats.failed == 1
        assert rt.stats.retries == 1        # bounded: exactly max_retries
    finally:
        monkeypatch.setenv("CODO_SERVE_FAULT", "")
        rt.close()


# --------------------------------------------------------------------------
# Deprecation shims (the launch/serve.py vs serving/serve.py split fix)
# --------------------------------------------------------------------------


def test_launch_serve_shim_warns_and_delegates():
    import repro.launch.serve as shim
    import repro.serving.cli as cli
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        shim = importlib.reload(shim)
    assert any(issubclass(w.category, DeprecationWarning)
               and "repro.serving.cli" in str(w.message) for w in caught)
    assert shim.main is cli.main
    assert shim.InputError is cli.InputError
    assert shim.load_input_env is cli.load_input_env
    assert shim.serve_artifact is cli.serve_artifact


def test_serving_serve_shim_warns_and_delegates():
    import repro.serving.generator as generator
    import repro.serving.serve as shim
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        shim = importlib.reload(shim)
    assert any(issubclass(w.category, DeprecationWarning)
               and "repro.serving.generator" in str(w.message)
               for w in caught)
    assert shim.Generator is generator.Generator
    assert shim.Request is generator.Request
    assert shim.build_serve_step is generator.build_serve_step
    assert shim.build_prefill_step is generator.build_prefill_step


# --------------------------------------------------------------------------
# Config knobs
# --------------------------------------------------------------------------


def test_serve_config_reads_env_knobs(monkeypatch):
    monkeypatch.setenv("CODO_SERVE_BATCH_WINDOW_MS", "7.5")
    monkeypatch.setenv("CODO_SERVE_MAX_QUEUE", "33")
    monkeypatch.setenv("CODO_SERVE_WORKERS", "2")
    cfg = ServeConfig.from_env()
    assert cfg.batch_window_ms == 7.5
    assert cfg.max_queue == 33
    assert cfg.workers == 2
    # overrides beat env; garbage falls back to defaults
    assert ServeConfig.from_env(workers=0).workers == 0
    monkeypatch.setenv("CODO_SERVE_MAX_QUEUE", "not-a-number")
    assert ServeConfig.from_env().max_queue == 256


def test_workers_refused_on_tpu_backend(monkeypatch):
    """A TPU belongs to one process: a spawned worker could not reach the
    chip the parent holds, so the runtime refuses workers there (and
    still serves in-process)."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ServeError, match="TPU backend"):
        ServingRuntime(ServeConfig(workers=2))
    ServingRuntime(ServeConfig(workers=0)).close()
