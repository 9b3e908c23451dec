"""The aligned clock (``spans.py``) and the readers of the program's spans
and counters, on three steps each of ``gpt2m-fwd`` and ``gpt2m-train``
recorded on a v5e with ``record_trace.py``; the readers that were there
before give the same values as before on the older recording."""

import bisect
import io
import json
from types import SimpleNamespace

import pytest

import harness
import spans
import trace
from conftest import BENCH, REPO

DATA = BENCH / "tests" / "data"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# Window counts of the recorded runs (record_trace.py's "counters").
RECORDED = {"gpt2m-fwd": {"dispatches": 3, "traces": 0},
            "gpt2m-train": {"dispatches": 9, "traces": 0}}


def _ev(name, start, duration, run_id=None):
    stats = [] if run_id is None else [("run_id", run_id)]
    return SimpleNamespace(name=name, start_ns=start, duration_ns=duration,
                           stats=stats)


def _profile(doc: dict):
    """Planes of a recorded document as the ``ProfileData`` attributes
    they stand for."""
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=ln["name"],
                            events=[_ev(*e) for e in ln["events"]])
            for ln in p["lines"]])
        for p in doc["planes"]])


def _recorded(cell: str):
    return _profile(json.loads(
        (DATA / f"{cell}-codo-3-steps.trace.json").read_text()))


def _metric(name: str, ctx: dict):
    return harness.Bench(REPO).metric(name).read(ctx)


@pytest.fixture(scope="module", params=sorted(RECORDED))
def cell(request):
    profile = _recorded(request.param)
    return SimpleNamespace(name=request.param, red=trace.reduce_profile(profile),
                           aligned=spans.align(profile))


def test_offset_bracket_is_not_empty(cell):
    al = cell.aligned
    assert al is not None
    assert len(al.brackets) == 1 and all(lo <= hi for _, lo, hi in al.brackets)
    assert 0 < al.width_us < 1000
    # Every run starts after its dispatch began, on the aligned clock.
    assert all(r.start + al.delta(r.start) >= r.dispatch for r in al.runs)


def test_program_and_outside_idle_make_the_device_idle(cell):
    """The idle time split by host span adds up to ``device_idle_share``
    of ``trace.py``, which shifts the device's ops instead of aligning
    them: within a point."""
    al = cell.aligned
    idle = 100 * (1 - cell.red.busy_s / cell.red.window_s)
    outside = 100 * sum(v for k, v in al.idle_by_span().items()
                        if not k.startswith("codo.") or k == spans.SYNC
                        ) / al.window_s
    program = _metric("program_idle_share", {"program": al})
    assert 0 < program < idle
    assert program + outside == pytest.approx(idle, abs=1.0)


def test_each_dispatch_runs_its_own_program(cell):
    progs = {r.program for r in cell.aligned.runs}
    want = {"gpt2m-fwd": {"block"},
            "gpt2m-train": {"loss_fwd", "loss_bwd", "loss_upd"}}[cell.name]
    assert progs == want
    assert len(cell.aligned.runs) == 3 * len(want)


def test_readers_of_the_program_spans_and_counters(cell):
    ctx = {"program": cell.aligned, "steps": 3,
           "counters": RECORDED[cell.name]}
    host_ms = _metric("program_host_ms", ctx)
    step_ms = cell.red.window_s / 3 * 1e3
    assert 0 < host_ms < step_ms
    assert _metric("dispatches_per_step", ctx) == {
        "gpt2m-fwd": 1, "gpt2m-train": 3}[cell.name]
    assert _metric("window_traces", ctx) == 0


def test_where_the_idle_time_goes(cell):
    """Forward: the host waits in ``bench.wait`` for most of the idle
    time.  Train: in the ``codo.sync`` at a step's end."""
    top = cell.aligned.top_gaps(1)[0][0]
    assert top == {"gpt2m-fwd": "bench.wait",
                   "gpt2m-train": spans.SYNC}[cell.name]


@pytest.mark.parametrize("name", ["program_host_ms", "program_idle_share",
                                  "dispatches_per_step", "window_traces"])
def test_readers_find_nothing_in_a_program_without_spans(name):
    """A program that keeps neither spans nor counters (before they were
    added) gives no value, and no error."""
    assert _metric(name, {"trace": None, "steps": 3}) is None


# A run of one program each ``period`` ns: the call at t, its dispatch at
# t+100 (enqueued at t+110), the wait from t+150 to t+900; on the device
# the run goes from t+200 to t+800 on a clock SKEW ns ahead of the host's
# and ``drift`` ns more for each ns, and the host's callbacks start at
# t+820.  ``scale`` stretches the times within a step.
SKEW = 5000


def _synthetic(callbacks_at=820, period=1000, drift=0.0, runs=3, scale=1):
    host, runtime, modules, ops = [], [], [], []
    for i in range(runs):
        t = period * i

        def ev(name, at, duration, *run):
            return _ev(name, t + at * scale, duration * scale, *run)

        start = t + 200 * scale
        start += SKEW + int(drift * start)
        host += [ev("bench.forward", 0, 150), ev("codo.call", 10, 130),
                 ev("codo.make_env", 20, 50), ev("codo.dispatch.blk", 100, 40),
                 ev("bench.wait", 150, 750)]
        runtime += [ev(spans.ENQUEUE, 110, 5, i),
                    ev(spans.DONE, callbacks_at, 5, i)]
        modules.append(_ev("jit_codo_blk(7)", start, 600 * scale, i))
        ops.append(_ev("%fusion.1 = f32[8] fusion()", start, 600 * scale))
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[
            SimpleNamespace(name="python", events=host),
            SimpleNamespace(name="runtime", events=runtime)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            SimpleNamespace(name=spans.MODULES_LINE, events=modules),
            SimpleNamespace(name=trace.OPS_LINE, events=ops)])])


def test_bracket_holds_the_true_offset_and_splits_idle_exactly():
    al = spans.align(_synthetic())
    assert al.brackets == [(200 + SKEW, -SKEW - 90, -SKEW + 20)]
    # Idle on the host's clock with the true offset: [0,200) and the 400 ns
    # between runs, then [2800, 2900); the bracket's middle moves it by 35.
    assert al.window == (0, 2900)
    assert sum(al.idle_by_span().values()) == pytest.approx(1.1e-6)
    assert al.program_host_s() == pytest.approx(3 * 130e-9)
    assert al.launch_us() == {"blk": pytest.approx((100 - 35) / 1e3)}


def test_drifting_clocks_are_bracketed_piece_by_piece():
    """Clocks 40 ppm apart move the offset by 58 us over 1.45 s, more than
    the 36 us between a run's bounds (enqueued 18 us before it starts, its
    callbacks 18 us after it ends); each quarter second has a bracket of
    its own, and it holds the true offset of each of its runs."""
    drift, scale = 40e-6, 200
    profile = _synthetic(callbacks_at=890, period=50_000_000, drift=drift,
                         runs=30, scale=scale)
    al = spans.align(profile, io.StringIO())
    assert al is not None and len(al.brackets) == 6
    starts = [b[0] for b in al.brackets]
    for r in al.runs:
        _, lo, hi = al.brackets[bisect.bisect_right(starts, r.start) - 1]
        true = r.dispatch + 100 * scale - r.start    # host - device time
        assert lo <= true <= hi
    log = io.StringIO()
    keep, spans.PIECE_NS = spans.PIECE_NS, 10 ** 10
    try:
        assert spans.align(profile, log) is None
    finally:
        spans.PIECE_NS = keep
    assert "is empty" in log.getvalue()


def test_empty_bracket_means_no_alignment():
    log = io.StringIO()
    assert spans.align(_synthetic(callbacks_at=150), log) is None
    assert "is empty" in log.getvalue()


def test_existing_readers_read_the_older_recording_as_before():
    """``gpt2m-fwd-3-steps.trace.json`` (the parent's) through the
    unchanged ``trace.py`` and the readers that were there before."""
    import test_trace
    red = trace.reduce_profile(test_trace._profile())
    calls = trace.kernel_calls("\n".join({n for _, _, n in red.ops[0]}))
    setup = harness.Setup(
        routes=[("flashattn.mha", 4 * 1024 ** 3, 16 * 2 ** 20, 1024 * 1024)],
        flops_per_step=28 * 2 ** 30)
    ctx = {"trace": red, "setup": setup, "peak": PEAK, "calls": calls,
           "steps": 3, "entry": "forward"}
    want = {"device_idle_share": 43.52589384445263,
            "flashattn_roofline": 13.228189680892468,
            "conv_roofline": None,
            "step_mfu": 8.437876029869345,
            "routed_kernels": 1}
    got = {name: _metric(name, ctx) for name in want}
    assert got == {k: v if v is None else pytest.approx(v, rel=1e-12)
                   for k, v in want.items()}
