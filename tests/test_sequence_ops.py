"""The sequence-model ops (RMSNorm, SiLU, softplus, exp, cumsum, reshape,
axis transpose, the causal mask, the causal conv1d, the GQA head
broadcast, broadcasting arithmetic, batched matmul over several leading
axes) traced through ``codo.compile`` against ``jax.numpy``; ``region``
tags and the scopes they name; the gate's ``declined`` counter; and
``grad=True`` refusing the ops that have no VJP."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import codo
from repro.core import frontend as F
from repro.core.autodiff import AutodiffError
from repro.core.graph import task_region
from repro.core.lowering import (LOWER_CACHE_STATS, FusionGroup, _build_steps,
                                  lower)


def _conv1d(x, w, b):
    k, s = w.shape[0], x.shape[0]
    xp = np.concatenate([np.zeros((k - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[i] * xp[i:i + s] for i in range(k))


def _mask(x):
    r, c = x.shape[-2:]
    return np.where(np.tri(r, c, dtype=bool), x, -np.inf)


# name -> (traced fn, input shapes, jnp reference of the inputs and the
# weights in creation order)
CASES = {
    "rmsnorm": (lambda x: F.rmsnorm(x, 1e-5), [(8, 32)],
                lambda x, w: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                         + 1e-5) * w),
    "silu": (F.silu, [(8, 32)], jax.nn.silu),
    "softplus": (F.softplus, [(8, 32)], jax.nn.softplus),
    "exp": (F.exp, [(8, 32)], jnp.exp),
    "cumsum": (lambda x: F.cumsum(x, 1), [(4, 8, 6)],
               lambda x: jnp.cumsum(x, 1)),
    "reshape_split": (lambda x: F.reshape(x, (4, 2, 4, 8)), [(8, 32)],
                      lambda x: x.reshape(4, 2, 4, 8)),
    "reshape_merge": (lambda x: F.reshape(x, (6, 16)), [(2, 3, 4, 4)],
                      lambda x: x.reshape(6, 16)),
    "reshape_unit": (lambda x: F.reshape(x, (4, 6, 1, 1)), [(4, 1, 6)],
                     lambda x: x.reshape(4, 6, 1, 1)),
    "transpose": (lambda x: F.transpose(x, (1, 2, 0)), [(3, 4, 5)],
                  lambda x: x.transpose(1, 2, 0)),
    "causal_mask": (F.causal_mask, [(2, 6, 6)], _mask),
    "strict_lower": (lambda x: F.causal_mask(x, diagonal=-1, fill=0.0),
                     [(2, 6, 5)],
                     lambda x: np.where(np.tri(6, 5, -1, dtype=bool), x, 0)),
    "broadcast_to": (lambda x: F.broadcast_to(x, (2, 4, 4)), [(2, 4, 1)],
                     lambda x: np.broadcast_to(x, (2, 4, 4))),
    "conv1d_causal": (lambda x: F.conv1d_causal(x, 4), [(16, 8)], _conv1d),
    "repeat_heads": (lambda x: F.repeat_heads(x, 3), [(2, 4, 5)],
                     lambda x: jnp.repeat(x, 3, axis=0)),
    "sub_outer": (lambda a, b: F.sub(a, b), [(3, 5, 1), (3, 1, 5)],
                  lambda a, b: a - b),
    "mul_lead": (lambda a, b: F.mul(a, b), [(4, 3, 5), (3, 1)],
                 lambda a, b: a * b),
    "add_bias": (lambda x: F.add(x, F.param((6,))), [(4, 6)],
                 lambda x, w: x + w),
    "matmul_4d": (lambda a, b: F.matmul(a, b), [(2, 3, 4, 5), (2, 3, 5, 6)],
                  lambda a, b: jnp.matmul(a, b, precision="highest")),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_op_matches_jnp(name):
    fn, shapes, ref = CASES[name]
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    program = codo.compile(fn, *shapes, cache=None)
    ws = [rng.standard_normal(b.shape).astype(np.float32)
          for b in program.source.weights()]
    program.bind(**{b.name: w for b, w in
                    zip(program.source.weights(), ws)})
    got = np.asarray(program(*xs))
    want = np.asarray(ref(*xs, *ws))
    # Reassociated f32 sums at most (cumsum, matmul): a few ulps.
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if not ws:                      # eager mode runs the same impls
        eager = fn(*[jnp.asarray(x) for x in xs])
        np.testing.assert_allclose(np.asarray(eager), want, rtol=1e-5,
                                   atol=1e-5)


def test_reshape_refuses_a_regrouping_with_no_affine_nest():
    with pytest.raises(F.TraceError, match="regroups"):
        F.trace(lambda x: F.reshape(x, (4, 6)), (6, 4))


def test_regions_tag_tasks_and_name_scopes():
    def fn(x):
        with F.region("mixer"):
            h = F.silu(F.fc(x, 8))
        with F.region("mlp"):
            with F.region("inner"):
                h = F.exp(h)
            h = F.fc(h, 8)
        return F.add(h, x)

    g = F.trace(fn, (4, 8))
    assert [task_region(t) for t in g.tasks] == [
        "mixer", "mixer", "inner", "mlp", None]
    # Each step is named after its task's region, whatever group it is in;
    # a region-less task keeps its ``g<gid>.<kernel>`` name.
    names = [t.name for t in g.tasks]
    groups = [FusionGroup(0, names[:2], ()), FusionGroup(1, names[2:], ())]
    for grp in groups:
        for n in grp.tasks:
            g.task(n).fused_group = grp.gid
    scopes = [scope for _n, scope, _f in _build_steps(g, groups, False)]
    assert scopes == ["mixer.g0.xla", "mixer.g0.xla", "inner.g1.xla",
                      "mlp.g1.xla", "g1.xla"]
    with pytest.raises(F.TraceError):
        with F.region("not a name"):
            pass


def _hlo_text(program) -> str:
    env = program.make_env(np.zeros((4, 8), np.float32))
    return program.lower().fn.lower(env).as_text(debug_info=True)


def test_region_scopes_reach_the_compiled_program():
    def fn(x):
        with F.region("mixer"):
            return F.fc(x, 8)

    assert "mixer.g0.xla" in _hlo_text(codo.compile(fn, (4, 8), cache=None))


def test_region_free_graphs_keep_their_scope_names():
    program = codo.compile(lambda x: F.fc(F.relu(F.fc(x, 8)), 8), (4, 8),
                           cache=None)
    text = _hlo_text(program)
    assert "g0.xla" in text and "mixer" not in text


def test_declined_counts_chains_by_pattern_and_reason():
    """On the CPU the gate prices the small FFN chain as a loss, and the
    backward-only ``mmgrad`` guard refuses its ``matmul → gelu`` prefix."""
    def fn(x):
        return F.fc(F.gelu(F.fc(x, 16)), 16)

    low = lower(codo.compile(fn, (16, 16), cache=None).compiled)
    assert not [r for g in low.groups for r in g.routes]
    assert LOWER_CACHE_STATS["declined"][low.name] == {
        ("streamfuse.mmchain", "cost"): 1,
        ("streamfuse.mmgrad", "infeasible"): 1}


@pytest.mark.parametrize("fn", [
    lambda x: F.mean_all(F.silu(x)),
    lambda x: F.mean_all(F.rmsnorm(x)),
    lambda x: F.mean_all(F.add(x, F.param((8,)))),
], ids=["silu", "rmsnorm", "broadcast-add"])
def test_grad_refuses_ops_without_a_vjp(fn):
    with pytest.raises(AutodiffError, match="silu|rmsnorm|broadcast"):
        codo.compile(fn, (4, 8), grad=True, cache=None)


def test_axis_transpose_vjp_inverts_the_permutation():
    def loss(x):
        t = F.transpose(F.reshape(F.mul(x, F.param((6, 4))), (2, 3, 4)),
                        (1, 2, 0))
        return F.mean_all(F.mul(F.reshape(t, (3, 8)), F.reshape(t, (3, 8))))

    rng = np.random.default_rng(0)
    x, w = (rng.standard_normal((6, 4)).astype(np.float32) for _ in "xw")
    step = codo.compile(loss, (6, 4), grad=True, cache=None)
    (name,) = step.param_names
    _loss, grads = step.value_and_grad(x, params={name: w})
    want = jax.grad(lambda v: jnp.mean((x * v) ** 2))(w)
    np.testing.assert_allclose(np.asarray(grads[name]), want, rtol=1e-5)


def test_four_dim_matmul_vjp():
    def loss(a):
        w = F.param((2, 3, 5, 6))
        return F.mean_all(F.reshape(F.matmul(F.reshape(a, (2, 3, 4, 5)), w),
                                    (24, 6)))

    rng = np.random.default_rng(0)
    a = rng.standard_normal((24, 5)).astype(np.float32)
    w = rng.standard_normal((2, 3, 5, 6)).astype(np.float32)
    step = codo.compile(loss, (24, 5), grad=True, cache=None)
    (name,) = step.param_names
    _loss, grads = step.value_and_grad(a, params={name: w})
    want = jax.grad(lambda v: jnp.mean(jnp.matmul(a.reshape(2, 3, 4, 5), v)))(w)
    np.testing.assert_allclose(np.asarray(grads[name]), want, rtol=1e-5,
                               atol=1e-7)
