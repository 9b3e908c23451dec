"""repro.api — the ``codo`` frontend: one callable from function to design.

This is the primary public API of the reproduction (``import codo`` works
too, via the ``src/codo.py`` alias):

.. code-block:: python

    import codo

    def model(x):
        h = codo.F.fc(x, 512, relu=True)
        return codo.F.fc(h, 512) + x

    program = codo.compile(model, (64, 512))   # trace -> codo_opt
    y = program(x_array)                       # lower + execute
    program.export("design.json")              # portable artifact
    program.diagnostics.table()                # per-pass timings
    program.cost.total_cycles                  # modeled latency

``compile`` traces the function (:mod:`repro.core.frontend`), runs the
six-pass ``codo_opt`` pipeline, and wraps the result in a
:class:`CompiledProgram`.  Traced graphs are structurally identical to
hand-built ones, so they share the same content-addressed compile cache —
compiling a function whose graph was already compiled (by anyone, through
any road) is a cache hit.

Calling convention: positional arrays bind to the traced function's
parameters in order; keyword arrays override any buffer (inputs *or*
weights) by name.  Weight buffers created inside ops default to the same
deterministic shape-keyed initializer eager mode uses
(:func:`repro.core.frontend.weight_init`), so ``codo.compile(fn)(x)``
equals ``fn(x)`` exactly; bind real parameters with
:meth:`CompiledProgram.bind`.

The low-level road — build a :class:`~repro.core.graph.DataflowGraph` by
hand (``GB``) and call :func:`~repro.core.compiler.codo_opt` — remains
fully supported; ``compile`` accepts a ready graph too.

Smoke CLI (used by the CI compile-smoke job)::

    PYTHONPATH=src python -m repro.api gemm --cache-dir .codo_cache --run
"""

from __future__ import annotations

import argparse
from typing import Any, Callable, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import frontend
from repro.core.compiler import (CodoOptions, CompiledDataflow, _UNSET,
                                 codo_opt)
from repro.core.graph import DataflowGraph

# Re-exports: `codo.trace`, `codo.buffer`, `codo.ShapedBuffer`, and the op
# namespace as `codo.F` (also importable as `from repro.core import
# frontend as F`).
F = frontend
ShapedBuffer = frontend.ShapedBuffer
buffer = frontend.buffer
trace = frontend.trace
TraceError = frontend.TraceError


class CompiledProgram:
    """A compiled dataflow design with a function calling convention.

    Wraps the :class:`~repro.core.compiler.CompiledDataflow` the pipeline
    produced plus the trace's io contract (which argument is which input
    buffer, which buffer comes back).  Lowering to an executable jax
    program happens lazily on first call and is memoized by the lowering
    cache, keyed on the design's structural hash.
    """

    def __init__(self, source: DataflowGraph, compiled: CompiledDataflow,
                 input_names: Sequence[str], output_names: Sequence[str]):
        self.source = source                  # pre-pass graph (the oracle)
        self.compiled = compiled
        self.input_names = list(input_names)
        self.output_names = list(output_names)
        self.origin: str | None = None        # trace provenance (v1.5)
        self._bindings: dict[str, Any] = {}
        self._lowered = None
        self._lowered_key = None
        self._sharding = None
        self._provenance: dict | None = None

    # ---- introspection ---------------------------------------------------
    @property
    def graph(self) -> DataflowGraph:
        """The optimized (post-pass) graph."""
        return self.compiled.graph

    @property
    def diagnostics(self):
        """Per-pass :class:`~repro.core.passes.CompileDiagnostics`."""
        return self.compiled.diagnostics

    @property
    def cost(self):
        """Modeled :class:`~repro.core.costmodel.GraphCost` of the design."""
        return self.compiled.final

    @property
    def speedup(self) -> float:
        return self.compiled.speedup

    @property
    def fifo_fraction(self) -> float:
        return self.compiled.fifo_fraction

    @property
    def compile_seconds(self) -> float:
        return self.compiled.compile_seconds

    @property
    def schedule_report(self):
        return self.compiled.schedule_report

    @property
    def cache_hit(self) -> bool:
        return self.compiled.cache_hit

    def report(self) -> str:
        return self.compiled.report()

    def __repr__(self) -> str:
        ins = ", ".join(self.input_names)
        outs = ", ".join(self.output_names)
        return (f"CompiledProgram({self.graph.name}: ({ins}) -> ({outs}), "
                f"speedup {self.speedup:.1f}x, "
                f"{'cache hit' if self.cache_hit else 'compiled'})")

    # ---- parameters ------------------------------------------------------
    def bind(self, **arrays) -> "CompiledProgram":
        """Attach concrete values for weight (or input) buffers by name.
        Unbound weights fall back to the deterministic shape-keyed
        initializer shared with eager mode."""
        for name, value in arrays.items():
            buf = self.graph.buffers.get(name)
            if buf is None or buf.kind not in ("weight", "input"):
                known = sorted(b.name for b in self.graph.buffers.values()
                               if b.kind in ("weight", "input"))
                raise KeyError(f"no bindable buffer {name!r}; "
                               f"inputs/weights: {known}")
            self._check(buf, value)
            self._bindings[name] = value
        return self

    @staticmethod
    def _check(buf, value) -> None:
        shape = tuple(getattr(value, "shape", ()))
        if shape != tuple(buf.shape):
            raise ValueError(f"buffer {buf.name!r} expects shape "
                             f"{tuple(buf.shape)}, got {shape}")

    # ---- sharding --------------------------------------------------------
    @property
    def sharding(self):
        """The :class:`~repro.distributed.plan.ShardingPlan`, or None for
        a single-device program."""
        return self._sharding

    def shard(self, mesh, strategy: str = "auto") -> "CompiledProgram":
        """Partition this design across ``mesh`` (a jax ``Mesh`` or a
        pure-data :class:`~repro.distributed.plan.MeshSpec`).  The plan
        enters the lowering memo key, travels in the v1.4 artifact, and
        subsequent calls execute via ``shard_map`` with the plan's
        collective schedule.  ``shard(None)`` reverts to single-device."""
        if mesh is None:
            self._sharding = None
        else:
            from repro.distributed.partition import partition
            self._sharding = partition(self.compiled, mesh, strategy)
        self._lowered = None
        return self

    # ---- execution -------------------------------------------------------
    def lower(self, jit: bool = True):
        """The lowered executable program (memoized per jit flag and
        sharding-plan digest)."""
        plan = self._sharding
        key = (bool(jit), plan.digest() if plan is not None else "")
        if self._lowered is None or self._lowered_key != key:
            from repro.core.lowering import lower  # lazy: jax
            self._lowered = lower(self.compiled, jit=jit, sharding=plan)
            self._lowered_key = key
        return self._lowered

    def make_env(self, *arrays, **named) -> dict[str, Any]:
        """The full execution environment for one call: positional arrays
        mapped onto the traced inputs, keyword overrides, bound weights,
        and shape-keyed defaults for the rest."""
        if len(arrays) > len(self.input_names):
            raise TypeError(f"{self.graph.name} takes {len(self.input_names)} "
                            f"positional inputs {self.input_names}, "
                            f"got {len(arrays)}")
        env = dict(self._bindings)
        for name, value in zip(self.input_names, arrays):
            self._check(self.graph.buffers[name], value)
            env[name] = value
        for name, value in named.items():
            buf = self.graph.buffers.get(name)
            if buf is None or buf.kind not in ("input", "weight"):
                known = sorted(b.name for b in self.graph.buffers.values()
                               if b.kind in ("input", "weight"))
                raise KeyError(f"no bindable buffer {name!r} (intermediates "
                               f"are produced by the design and cannot be "
                               f"overridden); inputs/weights: {known}")
            self._check(buf, value)
            env[name] = value
        missing = [n for n in self.input_names if n not in env]
        if missing:
            raise TypeError(f"missing inputs {missing} "
                            f"(signature: {self.input_names})")
        for b in self.graph.weights():
            if b.name not in env:
                env[b.name] = frontend.weight_init(b.shape, b.dtype)
        return env

    def __call__(self, *arrays, jit: bool = True, **named):
        """Run the compiled design.  Returns one array per traced output
        (a bare array for single-output programs, a tuple otherwise)."""
        with TraceAnnotation("codo.call"):
            lowered = self.lower(jit=jit)
            with TraceAnnotation("codo.make_env"):
                env = self.make_env(*arrays, **named)
            out = lowered(env)
            vals = tuple(out[n] for n in self.output_names)
        return vals[0] if len(vals) == 1 else vals

    def verify(self, *arrays, rtol: float | None = None,
               atol: float | None = None, **named):
        """Check the lowered design against the un-optimized oracle (the
        source graph executed task by task) on these inputs.  A sharded
        program is verified through its multi-device lowering; the default
        tolerance widens to the documented fp-reassociation band (psum
        tree-reduces device partials, and local-shape matmuls may contract
        in a different order) — see ``lowering.verify_sharding``."""
        sharded = self._sharding is not None
        rtol = (1e-4 if sharded else 1e-5) if rtol is None else rtol
        atol = (5e-5 if sharded else 1e-5) if atol is None else atol
        env = self.make_env(*arrays, **named)
        got = self.lower(jit=False)(env)
        want = self.source.execute(env)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), rtol=rtol, atol=atol,
                err_msg=f"output {k} diverged after lowering")

    # ---- artifacts -------------------------------------------------------
    def export(self, path: str | None = None, *,
               weights: "bool | dict | None" = None, sidecar: bool = False):
        """Write (or return) the versioned JSON artifact of this design
        (docs/artifact_format.md).

        ``weights=True`` embeds every weight buffer's concrete array —
        bound values first, the deterministic initializer for the rest —
        so the artifact is a *self-contained served model* (v1.3
        ``weights`` section; ``codo.load`` binds them back, no
        ``weight_init`` needed at the serving end).  Pass a dict to ship
        specific arrays, and ``sidecar=True`` to write them to
        ``<path>.weights.npz`` instead of base64-in-JSON.

        A sharded program additionally writes its :class:`ShardingPlan`
        into the v1.4 ``sharding`` section, so ``codo.load`` reproduces
        the multi-device program on any host with enough devices.

        v1.5 artifacts also carry a ``provenance`` section — the
        *pre-pass* source graph's structural hash plus the trace origin —
        so ``artifact diff`` can tell "same source, different pipeline"
        from "different source"."""
        from repro.core.artifact import export_artifact  # lazy
        if weights is True:
            weights = {b.name: (self._bindings.get(b.name)
                                if b.name in self._bindings
                                else frontend.weight_init(b.shape, b.dtype))
                       for b in self.graph.weights()}
        return export_artifact(self.compiled, path, weights=weights,
                               weights_sidecar=sidecar,
                               sharding=self._sharding,
                               provenance=self.provenance())

    def provenance(self) -> dict:
        """The v1.5 ``provenance`` section: pre-pass source structural hash
        plus trace origin.  Loaded programs return the section stored in
        their artifact (the post-pass graph is not the source)."""
        if self._provenance is not None:
            return dict(self._provenance)
        return {"source_structural_hash": self.source.structural_hash(),
                "origin": self.origin or f"graph:{self.source.name}"}

    # ---- autodiff --------------------------------------------------------
    def value_and_grad(self, *, opt=None, wrt=None) -> "CompiledTrainStep":
        """Differentiate this program's source graph and compile the
        forward/backward/update triple through the same pass pipeline —
        the method form of ``codo.compile(fn, ..., grad=True)``."""
        step = _compile_train_step(self.source, options=self.compiled.options,
                                   opt=opt, wrt=wrt, origin=self.origin)
        return step


def _io_from_graph(graph: DataflowGraph) -> tuple[list[str], list[str]]:
    return ([b.name for b in graph.inputs()],
            [b.name for b in graph.outputs()])


class CompiledTrainStep:
    """A training step compiled end-to-end through the pass pipeline.

    Three linked :class:`CompiledProgram`\\ s — ``forward`` (loss +
    residuals), ``backward`` (cotangent walk, built by
    :mod:`repro.core.autodiff`), ``update`` (AdamW + global-norm clip +
    warmup-cosine schedule as registry ops) — each individually traced
    through fusion, fine-violation elimination, cost-gated kernel routing
    and the compile cache.  The backward graph's matmul→gradient-epilogue
    chains route to the ``streamfuse.mmgrad`` kernel when the cost gate
    approves (or under ``CODO_FORCE_PALLAS=1``).

    Numerical contract (the documented fp band, see docs/autodiff.md):
    against eager ``jax.grad`` + ``training.optimizer.adamw_update`` the
    compiled loss is bit-exact, gradients match within
    ``rtol=2e-3, atol=1e-4`` (fp32 reassociation across contractions),
    and the update math *given identical gradients* is bit-tight
    (observed ≤3e-8).
    """

    def __init__(self, source: DataflowGraph, graphs,
                 forward: CompiledProgram, backward: CompiledProgram,
                 update: CompiledProgram):
        self.source = source            # pre-pass loss graph (the oracle)
        self.graphs = graphs            # core.autodiff.TrainGraphs
        self.forward = forward
        self.backward = backward
        self.update = update
        self.input_names = [b.name for b in source.inputs()]
        self.param_names = list(graphs.params)
        self.origin: str | None = None
        self._provenance: dict | None = None
        self._initial_params: dict | None = None   # artifact-carried weights

    # ---- state -----------------------------------------------------------
    def init_params(self) -> dict:
        """Deterministic initial parameters (shape-keyed ``weight_init``),
        or the weight payload carried by the artifact this step was loaded
        from."""
        if self._initial_params is not None:
            return dict(self._initial_params)
        return {b.name: frontend.weight_init(b.shape, b.dtype)
                for b in self.source.weights()
                if b.name in set(self.param_names)}

    def init_opt_state(self, params: dict | None = None) -> dict:
        """Fresh AdamW state in ``training.optimizer`` checkpoint format:
        ``{"m": {...}, "v": {...}, "step": int32 scalar}``."""
        params = params if params is not None else self.init_params()
        return {"m": {w: np.zeros(np.shape(params[w]), np.float32)
                      for w in self.param_names},
                "v": {w: np.zeros(np.shape(params[w]), np.float32)
                      for w in self.param_names},
                "step": np.zeros((), np.int32)}

    # ---- execution -------------------------------------------------------
    def value_and_grad(self, *arrays, params: dict | None = None,
                       jit: bool = True, **named):
        """Run the compiled forward + backward graphs; returns
        ``(loss, grads)`` with ``grads`` keyed by parameter name."""
        g = self.graphs
        params = dict(params) if params is not None else self.init_params()
        with TraceAnnotation("codo.train.forward"):
            fenv = self.forward.make_env(*arrays, **params, **named)
            fouts = self.forward.lower(jit=jit)(fenv)
        with TraceAnnotation("codo.train.backward"):
            benv = {g.seeds[g.loss]: np.ones((1, 1), np.float32)}
            for r in g.residuals:
                benv[r] = fouts[r] if r in fouts else fenv[r]
            bouts = self.backward.lower(jit=jit)(benv)
        grads = {w: bouts[g.grads[w]] for w in self.param_names}
        return fouts[g.loss], grads

    def step(self, params: dict, opt_state: dict, *arrays,
             jit: bool = True, **named):
        """One full training step: forward, backward, AdamW update.
        Returns ``(new_params, new_opt_state, metrics)`` where metrics
        carries scalar ``loss``, ``grad_norm`` and ``lr``.

        ``codo.sync`` spans mark where the host waits for the device: each
        conversion of a device array to a host value.  The step counter is
        read before the first dispatch, so the host never waits between
        the phases of one step."""
        with TraceAnnotation("codo.train.step"):
            with TraceAnnotation("codo.sync"):
                counter = np.asarray(opt_state["step"], np.float32).reshape(1, 1)
            loss, grads = self.value_and_grad(*arrays, params=params,
                                              jit=jit, **named)
            with TraceAnnotation("codo.train.update"):
                uenv = {"step": counter}
                for w in self.param_names:
                    uenv[w] = params[w]
                    uenv[f"grad_{w}"] = grads[w]
                    uenv[f"m_{w}"] = opt_state["m"][w]
                    uenv[f"v_{w}"] = opt_state["v"][w]
                uouts = self.update.lower(jit=jit)(uenv)
            with TraceAnnotation("codo.sync"):
                new_step = np.asarray(uouts["new_step"], np.float32)
                metrics = {"loss": np.asarray(loss).reshape(()),
                           "grad_norm": np.asarray(uouts["grad_norm"]
                                                   ).reshape(()),
                           "lr": np.asarray(uouts["lr"]).reshape(())}
        new_params = {w: uouts[f"new_{w}"] for w in self.param_names}
        new_state = {"m": {w: uouts[f"new_m_{w}"] for w in self.param_names},
                     "v": {w: uouts[f"new_v_{w}"] for w in self.param_names},
                     "step": new_step.reshape(()).astype(np.int32)}
        return new_params, new_state, metrics

    def verify(self, *arrays, params: dict | None = None,
               rtol: float = 2e-3, atol: float = 1e-4, **named):
        """Check compiled loss + gradients against eager ``jax.grad`` of
        the source graph on these inputs, within the documented fp band."""
        import jax  # lazy
        g = self.graphs
        params = dict(params) if params is not None else self.init_params()
        loss, grads = self.value_and_grad(*arrays, params=params, **named)
        base = dict(zip(self.input_names, arrays))
        base.update(named)

        def loss_fn(ps):
            return self.source.execute({**base, **ps})[g.loss].reshape(())

        ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params)
        np.testing.assert_allclose(
            np.asarray(loss).reshape(()), np.asarray(ref_loss),
            rtol=rtol, atol=atol, err_msg="loss diverged from eager jax.grad")
        for w in self.param_names:
            np.testing.assert_allclose(
                np.asarray(grads[w]), np.asarray(ref_grads[w]),
                rtol=rtol, atol=atol,
                err_msg=f"grad {w} diverged from eager jax.grad")

    # ---- tooling ---------------------------------------------------------
    def provenance(self) -> dict:
        if self._provenance is not None:
            return dict(self._provenance)
        return {"source_structural_hash": self.source.structural_hash(),
                "origin": self.origin or f"graph:{self.source.name}"}

    def export(self, path: str | None = None, *,
               weights: "bool | dict | None" = None):
        """Write (or return) the v1.5 *train-step* artifact: one JSON doc
        with ``kind: "train_step"``, a full per-phase artifact under
        ``phases.{forward,backward,update}``, and the linking ``train``
        section (loss/seed/residual/grad names + optimizer attrs) so
        ``codo.load`` reconstructs the executable step in a fresh
        interpreter.  ``weights=True`` embeds the parameters in the
        forward phase (v1.3 semantics)."""
        from repro.core.artifact import export_train_step_artifact  # lazy
        g = self.graphs
        if weights is True:
            weights = self.init_params()
        train = {"loss": g.loss, "seeds": dict(g.seeds),
                 "residuals": list(g.residuals), "grads": dict(g.grads),
                 "params": list(g.params), "opt": dict(g.opt)}
        return export_train_step_artifact(
            {"forward": self.forward.compiled,
             "backward": self.backward.compiled,
             "update": self.update.compiled},
            train, path, weights=weights, provenance=self.provenance())

    def report(self) -> str:
        lines = [f"train step {self.source.name}: "
                 f"{len(self.param_names)} params, "
                 f"{len(self.graphs.residuals)} residuals"]
        for tag, p in (("forward", self.forward), ("backward", self.backward),
                       ("update", self.update)):
            lines.append(f"-- {tag} " + "-" * max(1, 60 - len(tag)))
            lines.append(p.report())
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"<CompiledTrainStep {self.source.name} "
                f"params={len(self.param_names)} "
                f"fwd={len(self.forward.compiled.graph.tasks)}t "
                f"bwd={len(self.backward.compiled.graph.tasks)}t "
                f"upd={len(self.update.compiled.graph.tasks)}t>")


def _compile_train_step(source: DataflowGraph, *, options=None, cache=_UNSET,
                        opt=None, wrt=None, origin: str | None = None,
                        **codo_kwargs) -> CompiledTrainStep:
    from repro.core.autodiff import build_train_graphs  # lazy: jax via ops
    graphs = build_train_graphs(source, oc=opt, wrt=wrt)
    progs = []
    for phase in (graphs.forward, graphs.backward, graphs.update):
        compiled = codo_opt(phase, options, cache=cache, **codo_kwargs)
        progs.append(CompiledProgram(phase, compiled, *_io_from_graph(phase)))
    step = CompiledTrainStep(source, graphs, *progs)
    step.origin = origin or f"graph:{source.name}"
    return step


def compile(fn: Callable | DataflowGraph, *specs,  # noqa: A001 — the API name
            options: CodoOptions | None = None, name: str | None = None,
            cache=_UNSET, mesh=None,
            sharding_strategy: str = "auto", grad: bool = False,
            opt=None, wrt=None,
            **codo_kwargs) -> CompiledProgram:
    """Trace ``fn`` over ``specs`` (shape tuples / :func:`buffer` protos)
    and compile it through the ``codo_opt`` pipeline.

    ``fn`` may also be a ready :class:`DataflowGraph` (then ``specs`` must
    be empty) — the escape hatch for hand-built graphs.  ``options``
    defaults to the full opt5 pipeline; ``cache=None`` disables
    memoization for this call.  Extra keyword arguments are forwarded to
    :func:`~repro.core.compiler.codo_opt`.

    ``mesh`` (a jax ``Mesh`` or a
    :class:`~repro.distributed.plan.MeshSpec`) makes the result a
    *multi-device* program: the partitioner runs after the single-device
    pipeline (so the compile cache stays shared across meshes) and
    ``sharding_strategy`` picks the placement — ``"auto"`` prices every
    feasible candidate, or force one of ``replicate``/``dp``/``tp``/
    ``dp_tp``.  See docs/sharding.md.

    ``grad=True`` differentiates the (scalar-loss) graph instead: the
    reverse toposort walk in :mod:`repro.core.autodiff` emits the
    backward as a second dataflow graph, an AdamW update graph rides
    along (``opt`` — an ``OptConfig``, a dict of its fields, or ``None``
    for defaults; ``wrt`` restricts the parameter set), and all three
    compile through this same pipeline.  Returns a
    :class:`CompiledTrainStep`.  See docs/autodiff.md.
    """
    if isinstance(fn, DataflowGraph):
        if specs:
            raise TraceError("compile(graph) takes no input specs — the "
                             "graph already declares its buffers")
        source, ins, outs = fn, *_io_from_graph(fn)
        if name is not None and name != source.name:
            raise TraceError(f"compile(graph, name={name!r}) cannot rename "
                             f"graph {source.name!r}")
        origin = f"graph:{source.name}"
    else:
        source, ins, outs = frontend.trace_io(fn, *specs, name=name)
        origin = (f"traced:{getattr(fn, '__module__', '?')}."
                  f"{getattr(fn, '__qualname__', source.name)}")
    if grad:
        if mesh is not None:
            raise TraceError("grad=True does not compose with mesh= yet — "
                             "shard the phases individually")
        return _compile_train_step(source, options=options, cache=cache,
                                   opt=opt, wrt=wrt, origin=origin,
                                   **codo_kwargs)
    if opt is not None or wrt is not None:
        raise TraceError("opt=/wrt= only apply with grad=True")
    compiled = codo_opt(source, options, cache=cache, **codo_kwargs)
    program = CompiledProgram(source, compiled, ins, outs)
    program.origin = origin
    if mesh is not None:
        program.shard(mesh, sharding_strategy)
    return program


def load(path) -> CompiledProgram:
    """Reconstruct a :class:`CompiledProgram` from an exported artifact
    (path or parsed document) — no recompile, any process; op kinds
    resolve against this process's registry.  Bound-weight payloads (v1.3)
    are hash-verified and re-bound, so a weight-carrying artifact executes
    without ever reaching the shape-keyed initializer.

    A v1.5 *train-step* artifact (``kind: "train_step"``) reconstructs a
    :class:`CompiledTrainStep` instead — all three phase graphs plus the
    linking ``train`` section."""
    from repro.core.artifact import (TRAIN_STEP_KIND, artifact_weights,
                                     import_artifact, load_artifact)  # lazy
    doc = load_artifact(path)
    if doc.get("kind") == TRAIN_STEP_KIND:
        return _load_train_step(doc)
    path = doc
    compiled = import_artifact(path)
    # The artifact carries the optimized graph only; it is its own oracle.
    ins, outs = _io_from_graph(compiled.graph)
    program = CompiledProgram(compiled.graph, compiled, ins, outs)
    # Keep the stored provenance (the post-pass graph's hash is NOT the
    # pre-pass source hash) so re-exports round-trip the v1.5 section.
    program._provenance = doc.get("provenance")
    plan = getattr(compiled, "sharding_plan", None)
    if plan is not None:
        # v1.4 sharding section: restore the multi-device program as-is
        # (the jax Mesh is only rebuilt from the plan's MeshSpec at
        # execution time, so loading needs no devices).
        program._sharding = plan
    bound = artifact_weights(path)
    if bound:
        program.bind(**bound)
    return program


def _load_train_step(doc: dict) -> CompiledTrainStep:
    from repro.core.artifact import import_train_step  # lazy
    from repro.core.autodiff import TrainGraphs  # lazy
    phases, train, weights = import_train_step(doc)
    graphs = TrainGraphs(
        forward=phases["forward"].graph, backward=phases["backward"].graph,
        update=phases["update"].graph, loss=train["loss"],
        seeds=dict(train["seeds"]), residuals=list(train["residuals"]),
        grads=dict(train["grads"]), params=list(train["params"]),
        opt=dict(train["opt"]))
    progs = [CompiledProgram(c.graph, c, *_io_from_graph(c.graph))
             for c in (phases["forward"], phases["backward"],
                       phases["update"])]
    step = CompiledTrainStep(phases["forward"].graph, graphs, *progs)
    step._provenance = doc.get("provenance")
    if weights:
        step._initial_params = weights
    return step


# --------------------------------------------------------------------------
# Smoke CLI:  python -m repro.api gemm --cache-dir .codo_cache --run
# The CI compile-smoke job greps `cache_hit=False` / `cache_hit=True` from
# a cold + warm invocation pair to pin frontend/cache-key stability.
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    from repro.models.dataflow_models import KERNEL_BENCHES
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description="Compile one Table II kernel through codo.compile().")
    ap.add_argument("workload", choices=sorted(KERNEL_BENCHES),
                    help="traced kernel workload to compile")
    ap.add_argument("--opt", default="opt5",
                    help="CodoOptions preset (default opt5)")
    ap.add_argument("--cache-dir", default="",
                    help="disk compile-cache dir (cold/warm smoke)")
    ap.add_argument("--run", action="store_true",
                    help="also execute the design on random inputs and "
                         "verify against the oracle (imports jax)")
    ap.add_argument("--export", default="", metavar="PATH",
                    help="export the design as a JSON artifact")
    args = ap.parse_args(argv)

    from repro.core.cache import CompileCache
    cache = (CompileCache(disk_dir=args.cache_dir) if args.cache_dir
             else _UNSET)
    graph = KERNEL_BENCHES[args.workload]()
    program = compile(graph, options=CodoOptions.preset(args.opt),
                      cache=cache)
    print(program.report())
    print(f"codo.compile({args.workload}): cache_hit={program.cache_hit} "
          f"speedup={program.speedup:.1f}x "
          f"key={program.graph.structural_hash()[:12]}")
    if args.run:
        from repro.models.dataflow_models import random_inputs
        env = random_inputs(program.source)
        program.verify(**env)
        print(f"numerics verified against the oracle on "
              f"{sorted(n for n in env)} ✓")
    if args.export:
        program.export(args.export)
        print(f"artifact exported to {args.export}")
    return 0


__all__ = ["CodoOptions", "CompiledProgram", "CompiledTrainStep", "F",
           "ShapedBuffer",
           "TraceError", "buffer", "compile", "load", "trace"]


if __name__ == "__main__":
    from repro.core.cache import enable_jax_cache
    enable_jax_cache()
    raise SystemExit(main())
