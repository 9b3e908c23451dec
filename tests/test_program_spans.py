"""The program's own spans, counters and stable names (docs/architecture.md,
"Tracing"): a CPU profile of compiled calls and of one train step holds
the ``codo.*`` spans, nested and once per call; the lowering's counters
count dispatches and traces per program; jitted programs, their steps and
the Pallas kernels carry stable names into the compiled program."""

import glob
import re

import jax
import numpy as np
import pytest

import repro.api as codo
from repro.core import frontend as F
from repro.core.lowering import LOWER_CACHE_STATS, clear_lower_cache

S, D = 16, 32


def blk(x):
    return F.add(F.fc(F.relu(F.fc(x, 2 * D)), D), x)


def blk_loss(x, t):
    d = F.sub(blk(x), t)
    return F.mean_all(F.mul(d, d))


def _profile(tmp_path, fn) -> list[tuple[int, int, str]]:
    """Host ``codo.*`` spans of a profile of ``fn()``: (start, end, name)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in profile.planes:
        for line in plane.lines:
            spans += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       ev.name) for ev in line.events
                      if ev.name.startswith("codo.")]
    return sorted(spans)


def _inside(spans, outer: str, inner: str) -> list[int]:
    """For each ``outer`` span, how many ``inner`` spans lie within it."""
    return [sum(1 for s, e, n in spans if n == inner and os <= s and e <= oe)
            for os, oe, on in spans if on == outer]


def test_call_spans_nest_once_per_call(tmp_path):
    p = codo.compile(blk, (S, D), cache=None)
    x = np.ones((S, D), np.float32)
    jax.block_until_ready(p(x))

    def three():
        for _ in range(3):
            jax.block_until_ready(p(x))

    spans = _profile(tmp_path, three)
    names = [n for _, _, n in spans]
    assert names.count("codo.call") == 3
    assert _inside(spans, "codo.call", "codo.make_env") == [1, 1, 1]
    assert _inside(spans, "codo.call", "codo.dispatch.blk") == [1, 1, 1]
    assert set(names) == {"codo.call", "codo.make_env", "codo.dispatch.blk"}


def test_train_step_spans_nest(tmp_path):
    step = codo.compile(blk_loss, (S, D), (S, D), grad=True, cache=None)
    params = step.init_params()
    opt = step.init_opt_state(params)
    x = t = np.ones((S, D), np.float32)
    params, opt, _ = step.step(params, opt, x, t)     # compiles

    spans = _profile(tmp_path, lambda: step.step(params, opt, x, t))
    names = [n for _, _, n in spans]
    assert names.count("codo.train.step") == 1
    for phase, prog in (("forward", "blk_loss_fwd"),
                        ("backward", "blk_loss_bwd"),
                        ("update", "blk_loss_upd")):
        assert _inside(spans, "codo.train.step", f"codo.train.{phase}") == [1]
        assert _inside(spans, f"codo.train.{phase}",
                       f"codo.dispatch.{prog}") == [1]
    # The step counter before the first dispatch, the new counter and the
    # metrics after the last.
    assert _inside(spans, "codo.train.step", "codo.sync") == [2]
    first_sync = min(s for s, _, n in spans if n == "codo.sync")
    first_dispatch = min(s for s, _, n in spans
                         if n.startswith("codo.dispatch."))
    assert first_sync < first_dispatch


def test_dispatches_and_traces_are_counted_per_program():
    clear_lower_cache()
    p = codo.compile(blk, (S, D), cache=None)
    x = np.ones((S, D), np.float32)
    for n in (1, 2, 3):
        jax.block_until_ready(p(x))
        assert LOWER_CACHE_STATS["dispatches"]["codo_blk"] == n
        assert LOWER_CACHE_STATS["traces"]["codo_blk"] == 1
    # A new shape is a new program under the same name: traced once more.
    wide = codo.compile(blk, (2 * S, D), cache=None)
    for _ in range(2):
        jax.block_until_ready(wide(np.ones((2 * S, D), np.float32)))
    assert LOWER_CACHE_STATS["dispatches"]["codo_blk"] == 5
    assert LOWER_CACHE_STATS["traces"]["codo_blk"] == 2
    clear_lower_cache()
    assert not LOWER_CACHE_STATS["dispatches"]
    assert not LOWER_CACHE_STATS["traces"]


def _hlo(program, *arrays) -> str:
    lowered = program.lower()
    env = program.make_env(*arrays)
    return lowered.fn.lower(env).compile().as_text()


def test_jit_names_follow_the_graph():
    p = codo.compile(blk, (S, D), cache=None)
    assert p.lower().name == "codo_blk"
    assert _hlo(p, np.ones((S, D), np.float32)).startswith(
        "HloModule jit_codo_blk,")
    step = codo.compile(blk_loss, (S, D), (S, D), grad=True, cache=None)
    names = {q.lower().name for q in (step.forward, step.backward,
                                      step.update)}
    assert names == {"codo_blk_loss_fwd", "codo_blk_loss_bwd",
                     "codo_blk_loss_upd"}


@pytest.mark.parametrize("interpret", [False, True])
def test_compiled_program_carries_group_scopes(monkeypatch, interpret):
    """Every op sits under ``g<gid>.xla`` or, for a routed chain,
    ``g<gid>.<kernel>``; in interpret mode the Pallas kernel's own name
    (``streamfuse_mmchain``) shows beneath its group's scope."""
    monkeypatch.setenv("CODO_FORCE_PALLAS", "1")
    if interpret:
        monkeypatch.setenv("CODO_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("CODO_PALLAS_INTERPRET", raising=False)

    def mlp_res(x):
        h = F.fc(F.gelu(F.fc(x, 4 * D)), D)
        return F.relu(F.add(h, x))

    p = codo.compile(mlp_res, (128, D), cache=None)
    groups = p.lower().groups
    assert [r.kernel for g in groups for r in g.routes] == [
        "streamfuse.mmchain"]
    want = ({f"g{g.gid}.xla" for g in groups}
            | {f"g{g.gid}.{r.kernel}" for g in groups for r in g.routes})
    text = _hlo(p, np.ones((128, D), np.float32))
    names = {n for n in re.findall(r'op_name="jit\(codo_mlp_res\)/([^"/]+)',
                                   text) if not n.startswith("jit(")}
    assert names <= want
    assert any(n.endswith(".streamfuse.mmchain") for n in names)
    assert any(n.endswith(".xla") for n in names)
    assert ("/streamfuse_mmchain/" in text) == interpret
