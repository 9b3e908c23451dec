"""The contractions a configuration's plain reference is written with.

A reference takes an ``ops`` namespace and does every matmul and conv
through it, so that one reference serves at the precision the
configuration states (``HIGHEST`` for float32) and, as the control, at the
precision below it (``HIGH``: three bf16 passes).  ``bf16x3`` spells out
those three passes in float32 arithmetic, for backends that ignore the
precision flag (XLA's CPU backend): the control of the tests.  On the CPU
those three passes read far closer to ``HIGHEST`` than the chip's ``HIGH``
does on a train step (loss gap 2.5e-7 at 512 wide against 2.0e-5 on a
v5e), so the train test's control is ``bf16x1``, a single bf16 pass.
"""

from __future__ import annotations

from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp

NCHW = ("NCHW", "OIHW", "NCHW")


def _conv(x, w, stride: int, pad: int, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=NCHW, precision=precision)


def at(precision: str) -> SimpleNamespace:
    """``mm`` and ``conv`` at ``precision`` ("highest", "high", "default")."""
    p = jax.lax.Precision(precision)
    return SimpleNamespace(mm=partial(jnp.matmul, precision=p),
                           conv=partial(_conv, precision=p))


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def bf16x1() -> SimpleNamespace:
    """One bf16 pass: operands rounded to bf16, products summed in f32."""
    hp = jax.lax.Precision.HIGHEST

    def rnd(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    return SimpleNamespace(
        mm=lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=hp),
        conv=lambda x, w, stride, pad: _conv(rnd(x), rnd(w), stride, pad, hp))


def bf16x3() -> SimpleNamespace:
    """``HIGH`` written out: hi*hi + hi*lo + lo*hi of bf16 halves, each
    product exact in float32, summed in float32."""
    hp = jax.lax.Precision.HIGHEST

    def mm(a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        f = partial(jnp.matmul, precision=hp)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))

    def conv(x, w, stride, pad):
        (xh, xl), (wh, wl) = _split(x), _split(w)
        f = partial(_conv, stride=stride, pad=pad, precision=hp)
        return f(xh, wh) + (f(xh, wl) + f(xl, wh))

    return SimpleNamespace(mm=mm, conv=conv)
