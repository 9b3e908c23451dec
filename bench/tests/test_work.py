"""Operations and bytes against hand counts."""


import pytest

import harness
import work
from conftest import BENCH


def _model(name):
    return harness.load_module(BENCH / "configs" / f"{name}.py")


@pytest.fixture(scope="module")
def gpt2():
    import repro.api as codo
    program = codo.compile(_model("gpt2-medium-block").block, (1024, 1024),
                           cache=None)
    return program


def test_gpt2_block_flops(gpt2):
    # q, k, v, proj: 4 x 2*S*D*D; scores and p@v: 2 x 2*S*S*D; FFN: 2 x 2*S*D*4D
    S = D = 1024
    want = 4 * 2 * S * D * D + 2 * 2 * S * S * D + 2 * 2 * S * D * 4 * D
    assert work.graph_flops(gpt2.source) == want == 30_064_771_072


def test_flash_chain_work(gpt2):
    routes = harness.routing(gpt2.lower(jit=True))
    flash = [tasks for kernel, tasks in routes if kernel.startswith("flashattn")]
    assert len(flash) == 1
    flops, nbytes = work.chain_work(gpt2.graph, flash[0])
    assert flops == 2 * 2 * 1024 ** 3                  # q@kT and p@v
    assert nbytes == 4 * 1024 * 1024 * 4               # q, kT, v in; out


def test_resnet_stage1_conv_work():
    from repro.core.frontend import trace
    g = trace(_model("resnet18-224").block, (1, 3, 224, 224))
    convs = [t for t in g.tasks if t.spec is not None
             and t.spec.kind == "conv2d"]
    stage1 = convs[1]                                  # after the stem
    assert tuple(g.buffers[stage1.spec.outs[0]].shape) == (1, 64, 56, 56)
    flops, nbytes = work.chain_work(g, [stage1.name])
    assert flops == 2 * 64 * 56 * 56 * 64 * 3 * 3 == 231_211_008
    # padded input (1, 64, 58, 58), weights (64, 64, 3, 3), output, f32
    assert nbytes == 4 * (64 * 58 * 58 + 64 * 64 * 9 + 64 * 56 * 56)
    assert 3.5e9 < work.graph_flops(g) < 3.8e9


def test_least_seconds_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 10, peak) == (10.0, "flops")
    assert work.least_seconds(10, 1000, peak) == (100.0, "bytes")
