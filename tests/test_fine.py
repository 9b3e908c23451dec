"""Fine-grained violation elimination (Figs. 5-6) unit tests."""

import pytest

from repro.core import DataflowGraph, eliminate_fine, fine_violations, matmul_task, ewise_task
from repro.core.fine import generate_permutation, rewrite_reduction_write
from repro.core.patterns import (BROADCAST_REREAD, MULTI_WRITE,
                                 ORDER_MISMATCH, access_sig)


def _mm_chain():
    g = DataflowGraph("mm_chain")
    g.buffer("a", (8, 16), kind="input")
    g.buffer("b", (16, 8), kind="weight")
    g.buffer("c", (8, 8))
    g.buffer("o", (8, 8), kind="output")
    g.add_task(matmul_task("mm", "c", "a", "b", 8, 8, 16,
                           fn=lambda e: {"c": e["a"] @ e["b"]}))
    g.add_task(ewise_task("relu", "o", ["c"], (8, 8),
                          fn=lambda e: {"o": e["c"]}))
    return g


def test_reduction_rewriting_fixes_multiwrite():
    g = _mm_chain()
    kinds = {v.kind for v in fine_violations(g)}
    assert MULTI_WRITE in kinds
    rep = eliminate_fine(g)
    assert rep.reductions_rewritten
    assert MULTI_WRITE not in {v.kind for v in fine_violations(g)}
    mm = g.task("mm")
    # reduction dim moved innermost, write emitted once per element
    assert mm.loops[-1].var == "k"
    w = mm.writes_to("c")[0]
    assert w.enclosing == ("m", "n")
    assert mm.reduction_rewritten


def test_reduction_rewrite_idempotent():
    g = _mm_chain()
    mm = g.task("mm")
    assert rewrite_reduction_write(mm, "c")
    assert not rewrite_reduction_write(mm, "c")  # nothing left to hoist


def test_order_mismatch_permutation():
    """producer writes (i,j) row-major; consumer reads transposed order."""
    from repro.core.graph import Access, Loop, Task, idx

    g = DataflowGraph("perm")
    g.buffer("x", (8, 4), kind="input")
    g.buffer("m", (8, 4))
    g.buffer("o", (8, 4), kind="output")
    g.add_task(ewise_task("p", "m", ["x"], (8, 4), dim_names=["i", "j"],
                          fn=lambda e: {"m": e["x"]}))
    # consumer iterates (j, i) but reads m[i, j]
    c = Task("c", [Loop("j", 4), Loop("i", 8)],
             [Access("m", (idx("i"), idx("j")), False)],
             [Access("o", (idx("i"), idx("j")), True)],
             flops_per_iter=100.0,   # make consumer the reference loop
             fn=lambda e: {"o": e["m"]})
    g.add_task(c)
    kinds = {v.kind for v in fine_violations(g)}
    assert ORDER_MISMATCH in kinds
    rep = eliminate_fine(g)
    assert rep.permutations
    pm = rep.permutations[0]
    assert pm.target == "p" and pm.reference == "c"
    assert not fine_violations(g)
    # producer loop order now matches consumer arrival order (j outer)
    p = g.task("p")
    assert [l.var for l in p.loops] == ["j", "i"]


def test_broadcast_reread_cached():
    g = _mm_chain()
    # the lhs 'a' is an input (exempt); make it an intermediate to trigger
    g.buffers["a"].kind = "intermediate"
    g.buffer("a0", (8, 16), kind="input")
    g.add_task(ewise_task("ld", "a", ["a0"], (8, 16), dim_names=["m", "k"],
                          fn=lambda e: {"a": e["a0"]}))
    kinds = {v.kind for v in fine_violations(g)}
    assert BROADCAST_REREAD in kinds
    eliminate_fine(g)
    mm = g.task("mm")
    r = mm.reads_from("a")[0]
    assert r.enclosing is not None          # cached: read exactly once
    assert not access_sig(mm, r).repeats


# --------------------------------------------------------------------------
# The incremental fixpoint against the full re-check it replaced
# --------------------------------------------------------------------------


def _full_recheck_fine(graph, cap):
    """The fixpoint as first written: every edge's violations recomputed
    after each rewrite, the first rewrite that succeeds applied."""
    from repro.core.fine import FineReport, _attempt
    report = FineReport()
    for it in range(cap):
        report.iterations = it
        vs = fine_violations(graph)
        if not vs:
            break
        if not any(_attempt(graph, v, report) is not None for v in vs):
            break
    report.unresolved = [f"{v.kind}:{v.buffer}({v.producer}->{v.consumer})"
                         for v in fine_violations(graph)]
    return report


def _after_coarse(graph):
    from repro.core import eliminate_coarse
    eliminate_coarse(graph)
    return graph


def _stacked_gpt2(n):
    from repro.core import frontend as F
    from repro.models.dataflow_models import gpt2_block_fn

    def fn(x):
        for _ in range(n):
            x = gpt2_block_fn(x)
        return x
    return F.trace(fn, (128, 256), name=f"gpt2x{n}")


def _zoo():
    from repro.models import dataflow_models as dm
    out = dict(dm.KERNEL_BENCHES, **dm.RECURRENCE_BENCHES)
    out.update({k: dm.DNN_BENCHES[k] for k in ("resnet18", "mobilenet",
                                                "zfnet", "gpt2_block")})
    out["gpt2_block_loss"] = dm.gpt2_block_loss
    return out


@pytest.mark.parametrize("name", sorted(_zoo()))
def test_incremental_fixpoint_matches_the_full_recheck(name):
    """Identical rewrites, iterations, leftovers and resulting graph on
    every model-zoo graph, all of which finish under the old cap of 200."""
    from repro.core.fine import _MAX_ITERS
    build = _zoo()[name]
    a, b = _after_coarse(build()), _after_coarse(build())
    got = eliminate_fine(a)
    want = _full_recheck_fine(b, _MAX_ITERS)
    assert want.iterations < _MAX_ITERS - 1
    assert got.reductions_rewritten == want.reductions_rewritten
    assert [(p.target, p.reference, p.buffer, p.depth_map)
            for p in got.permutations] == [
        (p.target, p.reference, p.buffer, p.depth_map)
        for p in want.permutations]
    assert (got.unresolved, got.iterations) == (want.unresolved,
                                                want.iterations)
    assert a.structural_hash() == b.structural_hash()


def test_deep_graph_gets_every_rewrite_each_layer_gets_alone():
    """Twelve stacked GPT-2 blocks: each block's rewrites and its two
    violations that no rewrite resolves, twelve times over.  The old cap
    of 200 rewrites stopped this graph with blocks left unrewritten."""
    from repro.core.fine import _MAX_ITERS
    one = eliminate_fine(_after_coarse(_stacked_gpt2(1)))
    deep = eliminate_fine(_after_coarse(_stacked_gpt2(12)))
    assert len(deep.reductions_rewritten) == 12 * len(one.reductions_rewritten)
    assert len(deep.permutations) == 12 * len(one.permutations)
    assert len(deep.unresolved) == 12 * len(one.unresolved) == 24
    capped = _full_recheck_fine(_after_coarse(_stacked_gpt2(12)), _MAX_ITERS)
    assert capped.iterations == _MAX_ITERS - 1
    assert len(capped.unresolved) > len(deep.unresolved)
