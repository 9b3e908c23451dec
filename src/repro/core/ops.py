"""Declarative op registry: ``Task`` numerics as data, not closures.

Historically every :class:`~repro.core.graph.Task` carried its numeric
semantics as an opaque Python closure (``fn(env) -> {buf: array}``).  That
worked, but closures are not picklable, so

* on-disk compile-cache entries came back *stripped* — a cold-restart hit
  could be costed and verified but never lowered or executed, and
* the batch ablation driver was confined to thread pools — a ``Task``
  could not cross a process boundary.

This module replaces closures with an :class:`OpSpec` — a plain-data
record of *which* op a task computes (operand buffer names, output buffer
names, and attributes like stride/padding/axis) — plus a registry that
materializes the matching jnp implementation on demand.  ``Task.fn`` is
now a derived property: tasks that carry a spec re-derive their callable
after a pickle round-trip, in any process.

OpSpec fields
-------------

``kind``
    Registry key naming the implementation (``"conv2d"``, ``"matmul"``,
    ``"relu"``, ...).  Distinct from ``Task.op``, which is the *pattern
    class* the passes reason about (a ``"conv2d"`` and a ``"dwconv2d"``
    spec are both ``op="conv"`` tasks).
``ins``
    Operand buffer names, positional.  ``env[ins[i]]`` is the i-th input
    array at execution time.
``outs``
    Output buffer names.  The implementation returns ``{out: array}`` for
    every name in ``outs``.
``attrs``
    Plain-data attributes (ints, floats, bools, strings, tuples thereof):
    stride, padding, reduction axes, scale factors...  Attributes are part
    of :meth:`signature` and therefore of
    ``DataflowGraph.structural_signature()`` — a semantic constant that
    lives in an attr automatically keys the compile cache, so two graphs
    differing only in, say, a scale factor never collide.
``parts``
    Sub-specs for the composite ``"fused"`` kind (the coarse pass merges
    multi-producer violations by fusing producers; the fused node's
    semantics are the parts run in sequence).

Pickling contract
-----------------

An ``OpSpec`` must contain only plain data: strings, numbers, bools, and
(nested) tuples/dicts of those, plus child ``OpSpec`` records in
``parts``.  Never close over arrays, modules, or callables — the whole
point is that ``pickle.dumps(spec)`` round-trips across interpreters and
that :meth:`signature` is a stable content address.  Implementations
(registered callables) stay in *code*, keyed by ``kind``: unpickling a
spec in a fresh process finds the implementation in the registry of that
process, so ships of spec'd graphs between processes only require both
sides to import the same version of this module.

Registering a new op
--------------------

.. code-block:: python

    from repro.core.ops import OpSpec, register_op

    @register_op("axpy")
    def _axpy(spec, env):
        import jax.numpy as jnp  # lazy: keep repro.core importable sans jax
        a = spec.attrs.get("a", 1.0)
        x, y = (env[b] for b in spec.ins)
        return {spec.outs[0]: a * x + y}

    # builders then attach: Task(..., spec=OpSpec("axpy", (x, y), (out,),
    #                                             {"a": 2.0}))

Implementations take ``(spec, env)`` and return a dict mapping *every*
name in ``spec.outs`` to its array.  jax imports belong *inside* the
implementation body — graph construction and the whole compile pipeline
must stay importable (and process-pool-spawnable) without pulling in jax.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable

# --------------------------------------------------------------------------
# Spec
# --------------------------------------------------------------------------


class UnknownOpError(KeyError):
    """Raised when a spec names a kind with no registered implementation."""


def _plain(value: Any) -> Any:
    """Canonical plain-data view of an attr value (lists -> tuples)."""
    if isinstance(value, (list, tuple)):
        return tuple(_plain(v) for v in value)
    return value


@dataclass
class OpSpec:
    """Declarative numeric semantics of one task — see the module docstring
    for the field-by-field contract."""

    kind: str
    ins: tuple[str, ...] = ()
    outs: tuple[str, ...] = ()
    attrs: dict[str, Any] = field(default_factory=dict)
    parts: tuple["OpSpec", ...] = ()

    def __post_init__(self):
        self.ins = tuple(self.ins)
        self.outs = tuple(self.outs)
        self.parts = tuple(self.parts)
        self.attrs = {k: _plain(v) for k, v in dict(self.attrs).items()}

    # ---- data plumbing ---------------------------------------------------
    def renamed(self, alias: dict[str, str]) -> "OpSpec":
        """Pure-data buffer rename: every operand/output name found in
        ``alias`` maps old -> new, recursively through ``parts``.  This is
        the declarative replacement for ``retarget_fn``'s env-aliasing
        closure shim."""
        return OpSpec(
            self.kind,
            tuple(alias.get(b, b) for b in self.ins),
            tuple(alias.get(b, b) for b in self.outs),
            dict(self.attrs),
            tuple(p.renamed(alias) for p in self.parts),
        )

    def copy(self) -> "OpSpec":
        return dataclasses.replace(
            self, attrs=dict(self.attrs),
            parts=tuple(p.copy() for p in self.parts))

    def buffers(self) -> set[str]:
        out = set(self.ins) | set(self.outs)
        for p in self.parts:
            out |= p.buffers()
        return out

    # ---- content addressing ----------------------------------------------
    def signature(self) -> tuple:
        """Canonical nested-tuple view: feeds
        ``DataflowGraph.structural_signature()`` so op semantics —
        including attr constants — key the compile cache."""
        return (self.kind, self.ins, self.outs,
                tuple(sorted((k, repr(v)) for k, v in self.attrs.items())),
                tuple(p.signature() for p in self.parts))

    # ---- JSON serialization (docs/artifact_format.md `spec` object) ------
    def to_dict(self) -> dict:
        """Language-neutral JSON view.  Tuples inside ``attrs`` become JSON
        arrays; :meth:`from_dict` restores them through ``__post_init__``'s
        canonicalization, so ``from_dict(to_dict(s)).signature() ==
        s.signature()`` holds for every spec obeying the plain-data
        contract."""
        out: dict = {"kind": self.kind, "ins": list(self.ins),
                     "outs": list(self.outs), "attrs": dict(self.attrs)}
        if self.parts:
            out["parts"] = [p.to_dict() for p in self.parts]
        return out

    @classmethod
    def from_dict(cls, doc: dict) -> "OpSpec":
        return cls(doc["kind"], tuple(doc.get("ins", ())),
                   tuple(doc.get("outs", ())), dict(doc.get("attrs", {})),
                   tuple(cls.from_dict(p) for p in doc.get("parts", ())))


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

# kind -> implementation(spec, env) -> {out buffer: array}
_REGISTRY: dict[str, Callable[[OpSpec, dict], dict]] = {}

# Bumped on every registration so memoized *materialized* programs (e.g.
# the lower() cache) can detect that an implementation changed underneath
# them and rebuild instead of serving stale numerics.
_EPOCH = 0


def registry_epoch() -> int:
    return _EPOCH


def register_op(kind: str):
    """Decorator: register ``fn(spec, env) -> {out: array}`` under ``kind``.
    Re-registration replaces (kernels may override reference impls)."""

    def deco(fn: Callable[[OpSpec, dict], dict]):
        global _EPOCH
        _REGISTRY[kind] = fn
        _EPOCH += 1
        return fn

    return deco


def registered_ops() -> list[str]:
    return sorted(_REGISTRY)


def op_impl(kind: str) -> Callable[[OpSpec, dict], dict]:
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise UnknownOpError(
            f"no implementation registered for op kind {kind!r}; "
            f"registered: {registered_ops()}") from None


def materialize(spec: OpSpec) -> Callable[[dict], dict]:
    """Build the executable ``env -> {out: array}`` callable for ``spec``.

    Raises :class:`UnknownOpError` eagerly (at materialization, not first
    call) so a stale spec fails loudly when a cache entry is reloaded."""
    impl = op_impl(spec.kind)

    def fn(env: dict) -> dict:
        return impl(spec, env)

    fn.spec = spec  # introspection/debugging: which spec produced this fn
    return fn


# --------------------------------------------------------------------------
# Reference implementations (lazy jax imports: the registry itself — and
# everything that builds or compiles graphs — must import without jax).
# --------------------------------------------------------------------------


@register_op("identity")
def _identity(spec: OpSpec, env: dict) -> dict:
    return {spec.outs[0]: env[spec.ins[0]]}


@register_op("dup")
def _dup(spec: OpSpec, env: dict) -> dict:
    """Coarse-pass duplicator: one private stream copy per consumer."""
    src = env[spec.ins[0]]
    return {o: src for o in spec.outs}


@register_op("fused")
def _fused(spec: OpSpec, env: dict) -> dict:
    """Coarse-pass producer fusion: run ``parts`` in order, each seeing the
    accumulated scope (earlier writes staged and merged, per Fig. 4b)."""
    out: dict = {}
    scope = dict(env)
    for part in spec.parts:
        r = materialize(part)(scope)
        scope.update(r)
        out.update(r)
    return out


@register_op("zeros")
def _zeros(spec: OpSpec, env: dict) -> dict:
    """Init half of the Fig. 4b init/pad multi-producer pair: a zeroed
    canvas of ``attrs['shape']`` (no operands)."""
    import jax.numpy as jnp
    import numpy as np
    dtype = np.dtype(spec.attrs.get("dtype", "float32"))
    return {spec.outs[0]: jnp.zeros(tuple(int(s) for s in spec.attrs["shape"]),
                                    dtype)}


@register_op("fill_interior")
def _fill_interior(spec: OpSpec, env: dict) -> dict:
    """Fill half of the init/pad pair: writes the interior of the canvas
    the init producer staged under this spec's own output name (both in
    ``graph.execute``'s accumulating env and in the coarse pass's fused
    ``parts`` scope)."""
    import jax.numpy as jnp
    p = int(spec.attrs["pad"])
    x = env[spec.ins[0]]
    canvas = env.get(spec.outs[0])
    if canvas is None:
        n, c, h, w = x.shape
        canvas = jnp.zeros((n, c, h + 2 * p, w + 2 * p), x.dtype)
    return {spec.outs[0]:
            canvas.at[:, :, p:p + x.shape[2], p:p + x.shape[3]].set(x)}


@register_op("pad2d")
def _pad2d(spec: OpSpec, env: dict) -> dict:
    import jax.numpy as jnp
    p = int(spec.attrs["pad"])
    return {spec.outs[0]: jnp.pad(env[spec.ins[0]],
                                  ((0, 0), (0, 0), (p, p), (p, p)))}


@register_op("conv2d")
def _conv2d(spec: OpSpec, env: dict) -> dict:
    import jax
    s = int(spec.attrs.get("stride", 1))
    g = int(spec.attrs.get("groups", 1))
    y = jax.lax.conv_general_dilated(
        env[spec.ins[0]], env[spec.ins[1]], (s, s), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=g,
        precision=jax.lax.Precision.HIGHEST)
    return {spec.outs[0]: y}


@register_op("relu")
def _relu(spec: OpSpec, env: dict) -> dict:
    import jax.numpy as jnp
    return {spec.outs[0]: jnp.maximum(env[spec.ins[0]], 0)}


@register_op("gelu")
def _gelu(spec: OpSpec, env: dict) -> dict:
    import jax
    return {spec.outs[0]: jax.nn.gelu(env[spec.ins[0]])}


@register_op("add")
def _add(spec: OpSpec, env: dict) -> dict:
    return {spec.outs[0]: env[spec.ins[0]] + env[spec.ins[1]]}


@register_op("vadd")
def _vadd(spec: OpSpec, env: dict) -> dict:
    a = float(spec.attrs.get("alpha", 1.0))
    b = float(spec.attrs.get("beta", 1.0))
    return {spec.outs[0]: a * env[spec.ins[0]] + b * env[spec.ins[1]]}


@register_op("scale")
def _scale(spec: OpSpec, env: dict) -> dict:
    return {spec.outs[0]: env[spec.ins[0]] * float(spec.attrs["s"])}


@register_op("affine")
def _affine(spec: OpSpec, env: dict) -> dict:
    """``a*x + b`` — the scalar-operand form of add/sub (reflected-operator
    sugar).  With ``a`` in {1, -1} the result is bit-exact with the eager
    ``x + b`` / ``b - x`` expressions it stands in for."""
    a = float(spec.attrs.get("a", 1.0))
    b = float(spec.attrs.get("b", 0.0))
    x = env[spec.ins[0]]
    return {spec.outs[0]: (x if a == 1.0 else (-x if a == -1.0 else a * x)) + b}


@register_op("divc")
def _divc(spec: OpSpec, env: dict) -> dict:
    """``x / c`` — true division (not scale-by-reciprocal), so traced and
    eager results agree to the last ulp."""
    return {spec.outs[0]: env[spec.ins[0]] / float(spec.attrs["c"])}


@register_op("rdivc")
def _rdivc(spec: OpSpec, env: dict) -> dict:
    """``c / x`` — the scalar-left reflected division."""
    return {spec.outs[0]: float(spec.attrs["c"]) / env[spec.ins[0]]}


@register_op("div")
def _div(spec: OpSpec, env: dict) -> dict:
    return {spec.outs[0]: env[spec.ins[0]] / env[spec.ins[1]]}


@register_op("mul")
def _mul(spec: OpSpec, env: dict) -> dict:
    return {spec.outs[0]: env[spec.ins[0]] * env[spec.ins[1]]}


@register_op("const")
def _const(spec: OpSpec, env: dict) -> dict:
    """Materialize a compile-time constant array (array-left operands
    lifted into a trace).  The value lives in ``attrs`` as nested tuples —
    plain data, so it enters the structural signature and pickles."""
    import jax.numpy as jnp
    import numpy as np
    return {spec.outs[0]: jnp.asarray(np.array(
        spec.attrs["value"], dtype=np.dtype(spec.attrs.get("dtype",
                                                           "float32"))))}


@register_op("softmax")
def _softmax(spec: OpSpec, env: dict) -> dict:
    import jax
    axis = int(spec.attrs.get("axis", -1))
    return {spec.outs[0]: jax.nn.softmax(env[spec.ins[0]], axis)}


# Contractions (matmul, mv, conv and their VJPs) name their precision: on
# TPU an f32 contraction with implicit precision is a single bf16 pass.
@register_op("matmul")
def _matmul(spec: OpSpec, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    return {spec.outs[0]: jnp.matmul(env[spec.ins[0]], env[spec.ins[1]],
                                     precision=jax.lax.Precision.HIGHEST)}


@register_op("mv")
def _mv(spec: OpSpec, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    A = env[spec.ins[0]]
    if spec.attrs.get("trans", False):
        A = A.T
    return {spec.outs[0]: jnp.matmul(A, env[spec.ins[1]],
                                     precision=jax.lax.Precision.HIGHEST)}


@register_op("transpose")
def _transpose(spec: OpSpec, env: dict) -> dict:
    """2-D transpose by default; an optional ``perm`` attr generalizes to
    any rank (the frontend emits ``perm=(0, 2, 1)`` for batched operands)."""
    x = env[spec.ins[0]]
    perm = spec.attrs.get("perm")
    if perm is not None:
        return {spec.outs[0]: x.transpose(tuple(int(p) for p in perm))}
    return {spec.outs[0]: x.T}


@register_op("maxpool2d")
def _maxpool2d(spec: OpSpec, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    k = int(spec.attrs["k"])
    y = jax.lax.reduce_window(env[spec.ins[0]], -jnp.inf, jax.lax.max,
                              (1, 1, k, k), (1, 1, k, k), "VALID")
    return {spec.outs[0]: y}


@register_op("mean")
def _mean(spec: OpSpec, env: dict) -> dict:
    axes = tuple(int(a) for a in spec.attrs["axes"])
    return {spec.outs[0]: env[spec.ins[0]].mean(axis=axes)}


@register_op("reshape")
def _reshape(spec: OpSpec, env: dict) -> dict:
    shape = tuple(int(s) for s in spec.attrs["shape"])
    return {spec.outs[0]: env[spec.ins[0]].reshape(shape)}


@register_op("concat")
def _concat(spec: OpSpec, env: dict) -> dict:
    import jax.numpy as jnp
    axis = int(spec.attrs.get("axis", 0))
    return {spec.outs[0]: jnp.concatenate([env[b] for b in spec.ins],
                                          axis=axis)}


@register_op("split")
def _split(spec: OpSpec, env: dict) -> dict:
    """Multi-output inverse of concat: ``sizes`` partitions ``axis``.
    Pure indexing, so it stays tracer-safe under jit."""
    axis = int(spec.attrs.get("axis", 0))
    x = env[spec.ins[0]]
    out, off = {}, 0
    for o, s in zip(spec.outs, spec.attrs["sizes"]):
        ix = [slice(None)] * x.ndim
        ix[axis] = slice(off, off + int(s))
        out[o] = x[tuple(ix)]
        off += int(s)
    return out


@register_op("slice")
def _slice(spec: OpSpec, env: dict) -> dict:
    """Static rectangular window: ``starts``/``sizes`` per dimension."""
    x = env[spec.ins[0]]
    ix = tuple(slice(int(st), int(st) + int(sz))
               for st, sz in zip(spec.attrs["starts"], spec.attrs["sizes"]))
    return {spec.outs[0]: x[ix]}


@register_op("rmsnorm")
def _rmsnorm(spec: OpSpec, env: dict) -> dict:
    """``x / sqrt(mean(x², -1) + eps) * w`` over the last axis."""
    import jax
    import jax.numpy as jnp
    x, w = env[spec.ins[0]], env[spec.ins[1]]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return {spec.outs[0]: x * jax.lax.rsqrt(ms + float(spec.attrs["eps"])) * w}


@register_op("silu")
def _silu(spec: OpSpec, env: dict) -> dict:
    import jax
    return {spec.outs[0]: jax.nn.silu(env[spec.ins[0]])}


@register_op("softplus")
def _softplus(spec: OpSpec, env: dict) -> dict:
    import jax
    return {spec.outs[0]: jax.nn.softplus(env[spec.ins[0]])}


@register_op("exp")
def _exp(spec: OpSpec, env: dict) -> dict:
    import jax.numpy as jnp
    return {spec.outs[0]: jnp.exp(env[spec.ins[0]])}


@register_op("cumsum")
def _cumsum(spec: OpSpec, env: dict) -> dict:
    import jax.numpy as jnp
    return {spec.outs[0]: jnp.cumsum(env[spec.ins[0]],
                                     axis=int(spec.attrs["axis"]))}


@register_op("causal_mask")
def _causal_mask(spec: OpSpec, env: dict) -> dict:
    """``fill`` (−inf) where, in the last two axes, column − row exceeds
    ``diagonal`` (0: strictly above the diagonal)."""
    import jax.numpy as jnp
    x = env[spec.ins[0]]
    rows, cols = x.shape[-2:]
    keep = (jnp.arange(cols)[None, :] - jnp.arange(rows)[:, None]
            <= int(spec.attrs.get("diagonal", 0)))
    return {spec.outs[0]: jnp.where(keep, x, float(spec.attrs.get(
        "fill", -jnp.inf)))}


@register_op("conv1d_causal")
def _conv1d_causal(spec: OpSpec, env: dict) -> dict:
    """Depthwise causal convolution over the rows of ``x (S, C)`` with taps
    ``w (K, C)`` and bias ``b (C,)``: ``y[t] = b + Σ_k w[k] x[t - K + 1 + k]``,
    zero before the first row."""
    import jax.numpy as jnp
    x, w, b = (env[n] for n in spec.ins)
    k, s = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    y = b + sum(w[i] * xp[i:i + s] for i in range(k))
    return {spec.outs[0]: y}


@register_op("repeat_heads")
def _repeat_heads(spec: OpSpec, env: dict) -> dict:
    """Grouped-query broadcast: each head of axis 0 repeated ``groups``
    times in place, so head ``h`` of the result is head ``h // groups``."""
    import jax.numpy as jnp
    return {spec.outs[0]: jnp.repeat(env[spec.ins[0]],
                                     int(spec.attrs["groups"]), axis=0)}


def _rglru_reference(a, b):
    """RG-LRU recurrence h_t = a_t * h_{t-1} + b_t over axis 1 of (B, S, D)
    operands, h_{-1} = 0 — shared by the generic impl and its VJP (the VJP
    always differentiates this reference, so re-registering the forward
    with a kernel cannot change gradient semantics)."""
    import jax
    import jax.numpy as jnp

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros_like(a[:, 0]),
                         (jnp.swapaxes(a, 0, 1), jnp.swapaxes(b, 0, 1)))
    return jnp.swapaxes(hs, 0, 1)


def _ssd_reference(states, decay):
    """SSD inter-chunk recurrence over (nc, BH, P, N) end states and
    (nc, BH, 1, 1) decays: the state carried *into* each chunk, h_0 = 0.
    Shared by the generic impl and its VJP."""
    import jax
    import jax.numpy as jnp

    def step(h, inp):
        st, dec = inp
        return h * dec + st, h

    h0 = jnp.zeros(states.shape[1:], states.dtype)
    _, prevs = jax.lax.scan(step, h0, (states, decay))
    return prevs


@register_op("rglru_scan")
def _rglru_scan(spec: OpSpec, env: dict) -> dict:
    """RG-LRU linear recurrence h_t = a_t * h_{t-1} + b_t over axis 1 of
    (B, S, D) operands, h_{-1} = 0.  This is the *generic* sequential
    definition (``lax.scan``); the routed ``rglru.scan`` kernel replaces
    it with the chunked Pallas stream."""
    import jax.numpy as jnp
    return {spec.outs[0]: _rglru_reference(jnp.asarray(env[spec.ins[0]]),
                                           jnp.asarray(env[spec.ins[1]]))}


@register_op("ssd_scan")
def _ssd_scan(spec: OpSpec, env: dict) -> dict:
    """Mamba-2 SSD inter-chunk state recurrence over per-chunk end states
    (nc, BH, P, N) and scalar decays (nc, BH, 1, 1): emits the state
    carried *into* each chunk (h_0 = 0).  Generic sequential definition;
    the routed ``ssd.scan`` kernel is the chunked Pallas stream."""
    import jax.numpy as jnp
    return {spec.outs[0]: _ssd_reference(jnp.asarray(env[spec.ins[0]]),
                                         jnp.asarray(env[spec.ins[1]]))}


# --------------------------------------------------------------------------
# Gradient + optimizer op implementations (ISSUE 10).  Same contract as
# everything above: plain-data specs, lazy jax imports, ``(spec, env) ->
# {out: array}``.  These are the vocabulary the autodiff pass
# (core/autodiff.py) emits backward and AdamW-update graphs in — all of
# them first-class registry ops, so the backward graph pickles, caches,
# and reloads exactly like a forward graph.
# --------------------------------------------------------------------------


@register_op("mean_all")
def _mean_all(spec: OpSpec, env: dict) -> dict:
    """Full reduction to a (1, 1) scalar carrier — the loss head."""
    return {spec.outs[0]: env[spec.ins[0]].mean().reshape(1, 1)}


@register_op("bcast")
def _bcast(spec: OpSpec, env: dict) -> dict:
    """Broadcast to ``attrs['shape']`` (scalar carriers flatten first)."""
    import jax.numpy as jnp
    shape = tuple(int(s) for s in spec.attrs["shape"])
    x = env[spec.ins[0]]
    if x.size == 1:
        x = x.reshape(())
    return {spec.outs[0]: jnp.broadcast_to(x, shape)}


@register_op("outer")
def _outer(spec: OpSpec, env: dict) -> dict:
    """Rank-1 outer product ``a ⊗ b`` — the matrix grad of ``mv``."""
    a, b = env[spec.ins[0]], env[spec.ins[1]]
    return {spec.outs[0]: a[:, None] * b[None, :]}


@register_op("relu_grad")
def _relu_grad(spec: OpSpec, env: dict) -> dict:
    g, x = env[spec.ins[0]], env[spec.ins[1]]
    return {spec.outs[0]: g * (x > 0).astype(g.dtype)}


@register_op("gelu_grad")
def _gelu_grad(spec: OpSpec, env: dict) -> dict:
    """Exact (tanh-approx) gelu VJP via jax's own rule, so registry-vs-jax
    gradient parity is bit-tight."""
    import jax
    g, x = env[spec.ins[0]], env[spec.ins[1]]
    _, vjp = jax.vjp(jax.nn.gelu, x)
    return {spec.outs[0]: vjp(g)[0]}


@register_op("softmax_grad")
def _softmax_grad(spec: OpSpec, env: dict) -> dict:
    """``y * (g - sum(g*y, axis))`` with ``y`` the forward softmax output."""
    axis = int(spec.attrs.get("axis", -1))
    g, y = env[spec.ins[0]], env[spec.ins[1]]
    return {spec.outs[0]: y * (g - (g * y).sum(axis=axis, keepdims=True))}


@register_op("conv2d_input_grad")
def _conv2d_input_grad(spec: OpSpec, env: dict) -> dict:
    """Cotangent wrt the conv input: jax.vjp of the (linear) conv at a
    zero input — exact, and stays in lockstep with the forward lowering."""
    import jax
    import jax.numpy as jnp
    s = int(spec.attrs.get("stride", 1))
    groups = int(spec.attrs.get("groups", 1))
    x_shape = tuple(int(v) for v in spec.attrs["x_shape"])
    g, w = env[spec.ins[0]], env[spec.ins[1]]

    def fwd(x):
        return jax.lax.conv_general_dilated(
            x, w, (s, s), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups, precision=jax.lax.Precision.HIGHEST)

    _, vjp = jax.vjp(fwd, jnp.zeros(x_shape, g.dtype))
    return {spec.outs[0]: vjp(g)[0]}


@register_op("conv2d_weight_grad")
def _conv2d_weight_grad(spec: OpSpec, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    s = int(spec.attrs.get("stride", 1))
    groups = int(spec.attrs.get("groups", 1))
    w_shape = tuple(int(v) for v in spec.attrs["w_shape"])
    g, x = env[spec.ins[0]], env[spec.ins[1]]

    def fwd(w):
        return jax.lax.conv_general_dilated(
            x, w, (s, s), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups, precision=jax.lax.Precision.HIGHEST)

    _, vjp = jax.vjp(fwd, jnp.zeros(w_shape, g.dtype))
    return {spec.outs[0]: vjp(g)[0]}


@register_op("maxpool2d_grad")
def _maxpool2d_grad(spec: OpSpec, env: dict) -> dict:
    """Scatter the cotangent to each window's argmax (jax.vjp of the
    forward reduce_window at the *actual* input)."""
    import jax
    import jax.numpy as jnp
    k = int(spec.attrs["k"])
    g, x = env[spec.ins[0]], env[spec.ins[1]]

    def fwd(z):
        return jax.lax.reduce_window(z, -jnp.inf, jax.lax.max,
                                     (1, 1, k, k), (1, 1, k, k), "VALID")

    _, vjp = jax.vjp(fwd, x)
    return {spec.outs[0]: vjp(g)[0]}


@register_op("slice_grad")
def _slice_grad(spec: OpSpec, env: dict) -> dict:
    """Zero-embed the window cotangent back into the source shape."""
    import jax.numpy as jnp
    g = env[spec.ins[0]]
    x_shape = tuple(int(v) for v in spec.attrs["x_shape"])
    ix = tuple(slice(int(st), int(st) + int(sz))
               for st, sz in zip(spec.attrs["starts"], spec.attrs["sizes"]))
    return {spec.outs[0]: jnp.zeros(x_shape, g.dtype).at[ix].set(g)}


@register_op("mean_grad")
def _mean_grad(spec: OpSpec, env: dict) -> dict:
    """Spread ``g / count`` uniformly over the reduced axes."""
    import jax.numpy as jnp
    g = env[spec.ins[0]]
    x_shape = tuple(int(v) for v in spec.attrs["x_shape"])
    axes = tuple(int(a) for a in spec.attrs["axes"])
    count = 1
    for a in axes:
        count *= x_shape[a]
    return {spec.outs[0]: jnp.broadcast_to(
        jnp.expand_dims(g / count, axes), x_shape)}


@register_op("rglru_scan_grad")
def _rglru_scan_grad(spec: OpSpec, env: dict) -> dict:
    """(da, db) of the RG-LRU recurrence — jax.vjp of the shared
    sequential reference (itself a reverse scan)."""
    import jax
    g, a, b = (env[n] for n in spec.ins)
    _, vjp = jax.vjp(_rglru_reference, a, b)
    da, db = vjp(g)
    return {spec.outs[0]: da, spec.outs[1]: db}


@register_op("ssd_scan_grad")
def _ssd_scan_grad(spec: OpSpec, env: dict) -> dict:
    """(dstates, ddecay) of the SSD inter-chunk recurrence."""
    import jax
    g, states, decay = (env[n] for n in spec.ins)
    _, vjp = jax.vjp(_ssd_reference, states, decay)
    ds, dd = vjp(g)
    return {spec.outs[0]: ds, spec.outs[1]: dd}


@register_op("sumsq")
def _sumsq(spec: OpSpec, env: dict) -> dict:
    """f32 sum of squares to a (1, 1) carrier (global-norm partials —
    matches ``optimizer.clip_by_global_norm``'s per-leaf term)."""
    import jax.numpy as jnp
    x = env[spec.ins[0]]
    return {spec.outs[0]:
            jnp.sum(jnp.square(x.astype(jnp.float32))).reshape(1, 1)}


@register_op("clip_scale")
def _clip_scale(spec: OpSpec, env: dict) -> dict:
    """Global-norm clip factor from the summed squares: outs are
    ``(scale, norm)``, both (1, 1) carriers."""
    import jax.numpy as jnp
    max_norm = float(spec.attrs["max_norm"])
    norm = jnp.sqrt(env[spec.ins[0]])
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return {spec.outs[0]: scale, spec.outs[1]: norm}


@register_op("lr_sched")
def _lr_sched(spec: OpSpec, env: dict) -> dict:
    """Warmup + cosine decay, the exact ``optimizer.lr_at`` arithmetic.
    The input is the *already incremented* step (a (1, 1) f32 carrier),
    matching ``adamw_update``'s ``lr_at(state['step'] + 1, oc)`` call."""
    import jax.numpy as jnp
    a = spec.attrs
    lr0 = float(a["lr"])
    warm_n = float(a["warmup_steps"])
    total = float(a["total_steps"])
    frac = float(a["min_lr_frac"])
    step = env[spec.ins[0]].reshape(()).astype(jnp.float32)
    warm = lr0 * (step + 1.0) / max(warm_n, 1.0)
    prog = jnp.clip((step - warm_n) / max(total - warm_n, 1.0), 0.0, 1.0)
    cos = lr0 * (frac + (1.0 - frac) * 0.5 * (1.0 + jnp.cos(jnp.pi * prog)))
    return {spec.outs[0]:
            jnp.where(step < warm_n, warm, cos).reshape(1, 1)}


@register_op("adamw_step")
def _adamw_step(spec: OpSpec, env: dict) -> dict:
    """One decoupled-weight-decay Adam update for a single parameter —
    the exact per-leaf arithmetic of ``optimizer.adamw_update`` with the
    global clip ``scale`` and scheduled ``lr`` as (1, 1) operands.
    ins: (p, g, m, v, scale, lr, step2); outs: (p2, m2, v2)."""
    import jax.numpy as jnp
    a = spec.attrs
    b1, b2 = float(a["b1"]), float(a["b2"])
    eps, wd = float(a["eps"]), float(a["wd"])
    p, g, m, v, scale, lr, step2 = (env[n] for n in spec.ins)
    f32 = jnp.float32
    g32 = g.astype(f32) * scale.reshape(())
    step_f = step2.reshape(()).astype(f32)
    p32 = p.astype(f32)
    m2 = b1 * m.astype(f32) + (1.0 - b1) * g32
    v2 = b2 * v.astype(f32) + (1.0 - b2) * g32 * g32
    mh = m2 / (1.0 - b1 ** step_f)
    vh = v2 / (1.0 - b2 ** step_f)
    delta = mh / (jnp.sqrt(vh) + eps) + wd * p32
    p2 = p32 - lr.reshape(()) * delta
    return {spec.outs[0]: p2.astype(p.dtype),
            spec.outs[1]: m2, spec.outs[2]: v2}


# --------------------------------------------------------------------------
# VJP rules (ISSUE 10): kind -> rule, the same registry discipline as the
# implementations — rules live in *code* keyed by kind, while everything
# they emit is plain OpSpec *data* in a second DataflowGraph, so the
# backward pickles/caches/reloads like any forward graph and the whole
# pass pipeline (fusion, routing, caching) applies to it unchanged.
#
# A rule takes ``(spec, g, b)``:
#
# ``spec``
#     the forward task's OpSpec;
# ``g``
#     {out buffer -> cotangent buffer name} for the *live* outputs only
#     (outputs on a path to the loss; at least one, or the task is
#     skipped entirely);
# ``b``
#     the backward-graph builder (``core.autodiff`` passes its
#     ``_BwdBuilder``): ``b.shape(name)`` reports a buffer's shape,
#     ``b.res(name)`` imports a forward buffer as a shared residual, the
#     op helpers (``b.add/mul/scale/matmul/...``) emit spec'd tasks and
#     return the produced buffer name.
#
# Rules return the input cotangents either as ``{in buffer: cot buffer}``
# or as a ``[(in buffer, cot buffer)]`` pair list.  The pair list is
# *required* whenever one buffer can appear in several operand slots
# (``mul(x, x)``, ``matmul(x, x)``, ...): each pair accumulates
# separately, a dict would silently drop one term.
# --------------------------------------------------------------------------

# kind -> rule(spec, g, b) -> {in: cot} | [(in, cot)]
_VJP_REGISTRY: dict[str, Callable] = {}


def register_vjp(kind: str):
    """Decorator registering a VJP rule for ``kind`` (replace-on-repeat,
    like :func:`register_op`; does *not* bump the registry epoch — rules
    never change already-materialized forward numerics)."""

    def deco(fn: Callable):
        _VJP_REGISTRY[kind] = fn
        return fn

    return deco


def has_vjp(kind: str) -> bool:
    return kind in _VJP_REGISTRY


def differentiable_ops() -> list[str]:
    return sorted(_VJP_REGISTRY)


def vjp_rule(kind: str) -> Callable:
    try:
        return _VJP_REGISTRY[kind]
    except KeyError:
        raise UnknownOpError(
            f"no VJP rule registered for op kind {kind!r}; differentiable "
            f"kinds: {differentiable_ops()}") from None


@register_vjp("identity")
def _identity_vjp(spec, g, b):
    return {spec.ins[0]: g[spec.outs[0]]}


@register_vjp("dup")
def _dup_vjp(spec, g, b):
    return {spec.ins[0]: b.add_n([g[o] for o in spec.outs if o in g])}


@register_vjp("zeros")
def _zeros_vjp(spec, g, b):
    return {}


@register_vjp("const")
def _const_vjp(spec, g, b):
    return {}


def _pad_window_vjp(spec, g, b):
    p = int(spec.attrs["pad"])
    x_shape = b.shape(spec.ins[0])
    starts = (0, 0) + (p,) * (len(x_shape) - 2)
    return {spec.ins[0]: b.slice(g[spec.outs[0]], starts, x_shape)}


register_vjp("pad2d")(_pad_window_vjp)
register_vjp("fill_interior")(_pad_window_vjp)


@register_vjp("conv2d")
def _conv2d_vjp(spec, g, b):
    go = g[spec.outs[0]]
    x, w = spec.ins
    base = {"stride": int(spec.attrs.get("stride", 1)),
            "groups": int(spec.attrs.get("groups", 1))}
    dx = b.emit("conv2d_input_grad", (go, b.res(w)), (b.shape(x),),
                dict(base, x_shape=b.shape(x)), op="conv",
                flops=2.0)[0]
    dw = b.emit("conv2d_weight_grad", (go, b.res(x)), (b.shape(w),),
                dict(base, w_shape=b.shape(w)), op="conv",
                flops=2.0)[0]
    return {x: dx, w: dw}


@register_vjp("relu")
def _relu_vjp(spec, g, b):
    return {spec.ins[0]: b.ewise(
        "relu_grad", (g[spec.outs[0]], b.res(spec.ins[0])))}


@register_vjp("gelu")
def _gelu_vjp(spec, g, b):
    return {spec.ins[0]: b.ewise(
        "gelu_grad", (g[spec.outs[0]], b.res(spec.ins[0])), flops=12.0)}


@register_vjp("add")
def _add_vjp(spec, g, b):
    go = g[spec.outs[0]]
    return [(spec.ins[0], go), (spec.ins[1], go)]


@register_vjp("vadd")
def _vadd_vjp(spec, g, b):
    go = g[spec.outs[0]]
    al = float(spec.attrs.get("alpha", 1.0))
    be = float(spec.attrs.get("beta", 1.0))
    return [(spec.ins[0], go if al == 1.0 else b.scale(go, al)),
            (spec.ins[1], go if be == 1.0 else b.scale(go, be))]


@register_vjp("scale")
def _scale_vjp(spec, g, b):
    return {spec.ins[0]: b.scale(g[spec.outs[0]], float(spec.attrs["s"]))}


@register_vjp("affine")
def _affine_vjp(spec, g, b):
    a = float(spec.attrs.get("a", 1.0))
    go = g[spec.outs[0]]
    return {spec.ins[0]: go if a == 1.0 else b.scale(go, a)}


@register_vjp("divc")
def _divc_vjp(spec, g, b):
    return {spec.ins[0]: b.divc(g[spec.outs[0]], float(spec.attrs["c"]))}


@register_vjp("rdivc")
def _rdivc_vjp(spec, g, b):
    # d(c/x)/dx = -c/x^2 = -y^2/c with y the forward output residual.
    go = g[spec.outs[0]]
    c = float(spec.attrs["c"])
    y = b.res(spec.outs[0])
    return {spec.ins[0]: b.scale(b.mul(go, b.mul(y, y)), -1.0 / c)}


@register_vjp("div")
def _div_vjp(spec, g, b):
    go = g[spec.outs[0]]
    y = b.res(spec.outs[0])
    den = b.res(spec.ins[1])
    da = b.div(go, den)
    db = b.scale(b.div(b.mul(go, y), den), -1.0)
    return [(spec.ins[0], da), (spec.ins[1], db)]


@register_vjp("mul")
def _mul_vjp(spec, g, b):
    go = g[spec.outs[0]]
    xa, xb = spec.ins
    if xa == xb:
        return [(xa, b.scale(b.mul(go, b.res(xa)), 2.0))]
    return [(xa, b.mul(go, b.res(xb))), (xb, b.mul(go, b.res(xa)))]


@register_vjp("matmul")
def _matmul_vjp(spec, g, b):
    go = g[spec.outs[0]]
    A, B = spec.ins
    dA = b.matmul(go, b.transpose(b.res(B)))
    dB = b.matmul(b.transpose(b.res(A)), go)
    return [(A, dA), (B, dB)]


@register_vjp("mv")
def _mv_vjp(spec, g, b):
    go = g[spec.outs[0]]
    A, x = spec.ins
    trans = bool(spec.attrs.get("trans", False))
    if trans:                       # y = A.T @ x
        dA = b.outer(b.res(x), go)
        dx = b.mv(b.res(A), go, trans=False)
    else:                           # y = A @ x
        dA = b.outer(go, b.res(x))
        dx = b.mv(b.res(A), go, trans=True)
    return [(A, dA), (x, dx)]


@register_vjp("transpose")
def _transpose_vjp(spec, g, b):
    perm = spec.attrs.get("perm")
    if perm is None:                # the 2-D T is self-inverse
        return {spec.ins[0]: b.transpose(g[spec.outs[0]])}
    inverse = tuple(sorted(range(len(perm)), key=lambda i: perm[i]))
    return {spec.ins[0]: b.transpose(g[spec.outs[0]], inverse)}


@register_vjp("reshape")
def _reshape_vjp(spec, g, b):
    return {spec.ins[0]: b.gb.reshape(g[spec.outs[0]], b.shape(spec.ins[0]))}


@register_vjp("concat")
def _concat_vjp(spec, g, b):
    go = g[spec.outs[0]]
    if len(spec.ins) == 1:
        return [(spec.ins[0], go)]
    axis = int(spec.attrs.get("axis", 0))
    sizes = tuple(b.shape(i)[axis] for i in spec.ins)
    return list(zip(spec.ins, b.split(go, sizes, axis)))


@register_vjp("split")
def _split_vjp(spec, g, b):
    axis = int(spec.attrs.get("axis", 0))
    pieces = [g[o] if o in g else b.zeros(b.shape(o)) for o in spec.outs]
    return {spec.ins[0]: b.concat(pieces, axis)}


@register_vjp("slice")
def _slice_vjp(spec, g, b):
    x_shape = b.shape(spec.ins[0])
    attrs = {"starts": tuple(int(s) for s in spec.attrs["starts"]),
             "sizes": tuple(int(s) for s in spec.attrs["sizes"]),
             "x_shape": x_shape}
    return {spec.ins[0]: b.emit("slice_grad", (g[spec.outs[0]],),
                                (x_shape,), attrs, op="copy", flops=0.0)[0]}


@register_vjp("softmax")
def _softmax_vjp(spec, g, b):
    axis = int(spec.attrs.get("axis", -1))
    return {spec.ins[0]: b.ewise(
        "softmax_grad", (g[spec.outs[0]], b.res(spec.outs[0])),
        {"axis": axis}, flops=4.0)}


@register_vjp("maxpool2d")
def _maxpool2d_vjp(spec, g, b):
    x = spec.ins[0]
    x_shape = b.shape(x)
    return {x: b.emit("maxpool2d_grad", (g[spec.outs[0]], b.res(x)),
                      (x_shape,), {"k": int(spec.attrs["k"])},
                      op="pool")[0]}


@register_vjp("mean")
def _mean_vjp(spec, g, b):
    x_shape = b.shape(spec.ins[0])
    axes = tuple(int(a) for a in spec.attrs["axes"])
    return {spec.ins[0]: b.emit("mean_grad", (g[spec.outs[0]],), (x_shape,),
                                {"axes": axes, "x_shape": x_shape})[0]}


@register_vjp("mean_all")
def _mean_all_vjp(spec, g, b):
    x_shape = b.shape(spec.ins[0])
    count = 1
    for s in x_shape:
        count *= int(s)
    scaled = b.divc(g[spec.outs[0]], float(count))
    return {spec.ins[0]: b.emit("bcast", (scaled,), (x_shape,),
                                {"shape": x_shape}, op="copy", flops=0.0)[0]}


@register_vjp("rglru_scan")
def _rglru_scan_vjp(spec, g, b):
    a, bb = spec.ins
    da, db = b.emit("rglru_scan_grad",
                    (g[spec.outs[0]], b.res(a), b.res(bb)),
                    (b.shape(a), b.shape(bb)), op="scan", flops=4.0)
    return [(a, da), (bb, db)]


@register_vjp("ssd_scan")
def _ssd_scan_vjp(spec, g, b):
    st, dec = spec.ins
    ds, dd = b.emit("ssd_scan_grad",
                    (g[spec.outs[0]], b.res(st), b.res(dec)),
                    (b.shape(st), b.shape(dec)), op="scan", flops=4.0)
    return [(st, ds), (dec, dd)]


__all__ = ["OpSpec", "UnknownOpError", "differentiable_ops", "has_vjp",
           "materialize", "op_impl", "register_op", "register_vjp",
           "registered_ops", "vjp_rule"]
