"""The trace reduction, on three steps of the GPT-2 block's forward recorded
on a v5e and trimmed to the lines the reduction reads."""

import json
from types import SimpleNamespace

import pytest

import trace
import work
from conftest import BENCH

DATA = BENCH / "tests" / "data" / "gpt2m-fwd-3-steps.trace.json"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _profile():
    """The recorded planes as the ``ProfileData`` attributes they stand for."""
    doc = json.loads(DATA.read_text())
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=ln["name"], events=[ev(*e) for e in ln["events"]])
            for ln in p["lines"]])
        for p in doc["planes"]])


@pytest.fixture(scope="module")
def red():
    return trace.reduce_profile(_profile())


def _line(name):
    for p in _profile().planes:
        for ln in p.lines:
            if ln.name == name:
                return ln.events
    raise KeyError(name)


def test_window_is_the_host_spans(red):
    spans = [
        e for p in _profile().planes if p.name == "/host:CPU"
        for ln in p.lines for e in ln.events]
    assert len(spans) == 3
    start = min(e.start_ns for e in spans)
    end = max(e.start_ns + e.duration_ns for e in spans)
    assert red.window == (start, end)


def test_busy_is_the_programs_time(red):
    # The ops of one program run one after another: their union is the
    # programs' time on the device (XLA Modules), within the few ns that
    # the trace leaves between ops.
    modules = sum(e.duration_ns for e in _line("XLA Modules")) / 1e9
    assert red.busy_s == pytest.approx(modules, rel=0.01)
    assert 0.0 < red.busy_s < red.window_s


def test_gaps_and_busy_fill_the_window(red):
    idle = sum(s for _, s in red.gaps())
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
    assert red.top_gaps(10)[0][0] == "bench.step"


def test_device_clock_is_shifted_into_the_window(red):
    first_op = min(s for s, _, _ in red.ops[0])
    assert first_op >= red.window[0]


def test_kernel_calls_and_roofline(red):
    calls = trace.kernel_calls("\n".join({n for _, _, n in red.ops[0]}))
    assert calls == {"mha_kernel.1": 1024 * 1024}
    # The flash chain's work (test_work.py): 4.29 GFLOP, 16 MiB.
    routes = [("flashattn.mha", 4 * 1024 ** 3, 16 * 2 ** 20, 1024 * 1024)]
    share = work.roofline_share("flashattn", routes, calls, red.ops[0], PEAK)
    kernel = [e - s for s, e, n in red.ops[0] if n.startswith("%mha_kernel.1 ")]
    assert len(kernel) == 3
    least = 4 * 1024 ** 3 / 197e12        # bound by flops, not bytes
    assert share == pytest.approx(100 * len(kernel) * least / (sum(kernel) / 1e9))
    assert 0 < share < 100
    assert work.roofline_share("streamfuse.conv", routes, calls, red.ops[0],
                               PEAK) is None


def test_top_ops_are_labelled_short(red):
    names = [n for n, _ in red.top_ops(10)]
    assert "mha_kernel.1 f32[1,1024,1024] custom-call" in names
    assert all(len(n) < 80 for n in names)
