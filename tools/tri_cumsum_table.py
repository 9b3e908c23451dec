#!/usr/bin/env python3
"""Time granite's cumulative sums each way on a TPU: XLA's reduce-window
(``jnp.cumsum``) against the triangular matmul at each block width.

    PYTHONPATH=src python3 tools/tri_cumsum_table.py [--calls 20] [--out F]
        [--no-program]

Two measurements, each variant compiled, profiled and freed in turn:

* one Mamba-2 layer's segment-sum chain at the ``granite-4.0-h-micro-period``
  shapes (the steps broadcast along each row and kept strictly below the
  diagonal, the cumulative sum down the rows, the causal mask, ``exp``,
  the product with C·Bᵀ), and the chunk cumsum of the steps, jitted alone;
  here also with the block contraction written as an ``einsum`` that keeps
  the scanned axis in place, to see whether XLA fuses it otherwise;
* the whole lowered program of that configuration, with every cumsum task
  on one path, and its output against the plain reference at ``HIGHEST``.

Times are the medians of the ``XLA Modules`` events of one profile (device
clock); ``cumsum_us`` sums the ops whose compiled HLO ``op_name`` names the
cumsum (a fusion takes its root's).  Prints one JSON object; a table goes
to stderr.
"""

from __future__ import annotations

import argparse
import functools
import glob
import importlib.util
import json
import math
import re
import statistics
import sys
import tempfile
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.core import lowering  # noqa: E402

CONFIG = "granite-4.0-h-micro-period"
HIGHEST = jax.lax.Precision.HIGHEST
BLOCKS = (128, 256)


def variants() -> dict:
    """Variant name -> cumsum ``f(x, axis)``."""
    out = {"reduce_window": lambda x, axis: jnp.cumsum(x, axis)}
    for b in BLOCKS:
        out[f"tri_{b}"] = functools.partial(lowering.tri_cumsum, block=b)
    return out


def einsum_form(x, axis: int, block: int):
    """``tri_cumsum`` with the block contraction written as an ``einsum``
    that keeps the scanned axis in place (``x`` on the left); the layer's
    chain only, to see whether XLA fuses it differently."""
    axis %= x.ndim
    shape, n = x.shape, x.shape[axis]
    a, c = math.prod(shape[:axis]), math.prod(shape[axis + 1:])
    b = min(n, block)
    nb = -(-n // b)
    x = jnp.pad(x.reshape(a, n, c), ((0, 0), (0, nb * b - n), (0, 0)))
    x = x.reshape(a, nb, b, c)
    tri = jnp.tril(jnp.ones((b, b), x.dtype))
    y = jnp.einsum("ankc,ik->anic", x, tri, precision=HIGHEST)
    if nb > 1:
        upto = jnp.cumsum(x.sum(axis=2, keepdims=True), axis=1)
        y = y + jnp.pad(upto[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0)))
    return y.reshape(a, nb * b, c)[:, :n].reshape(shape)


def chain_variants() -> dict:
    """The program's variants and, for the layer's chain alone, the
    einsum form at each block."""
    out = variants()
    for b in BLOCKS:
        out[f"einsum_{b}"] = functools.partial(einsum_form, block=b)
    return out


def load_config():
    path = ROOT / "bench" / "configs" / f"{CONFIG}.py"
    spec = importlib.util.spec_from_file_location("granite_period", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = json.loads(path.with_suffix(".json").read_text())
    return mod, cfg


def segment_chain(cumsum, nc: int, h: int, n: int):
    """One layer's segment sums through to the decay-weighted C·Bᵀ."""
    rows = jnp.arange(n)[:, None]
    cols = jnp.arange(n)[None, :]

    def chain(steps, cb):               # (nc, h, n, 1), (nc, 1, n, n)
        below = jnp.where(rows > cols, jnp.broadcast_to(steps, (nc, h, n, n)),
                          0.0)
        with jax.named_scope("cumsum"):
            seg = cumsum(below, 2)
        return jnp.exp(jnp.where(rows >= cols, seg, -jnp.inf)) * cb
    return chain


def module_runs(profile) -> dict[str, list[tuple[int, int]]]:
    """Module name -> (start, end) ns of each run on the first TPU."""
    out: dict[str, list[tuple[int, int]]] = defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        out[ev.name.split("(")[0]].append(
                            (s, s + int(ev.duration_ns)))
    return out


def op_times(profile) -> dict[str, float]:
    """HLO instruction name -> summed device us over the profile."""
    tot: dict[str, float] = defaultdict(float)
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        tot[ev.name.split(" ")[0].lstrip("%")] += (
                            ev.duration_ns / 1e3)
    return dict(tot)


_META = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"',
                   re.M)


def profiled(exe, args, calls: int):
    """Median device us of a run of ``exe``, the device us a run of the ops
    its HLO scopes to a cumsum, and the heaviest ops."""
    jax.block_until_ready(exe(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            jax.block_until_ready(exe(*args))
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        profile = jax.profiler.ProfileData.from_file(path)
    runs = [r for rs in module_runs(profile).values() for r in rs]
    ops = {k: v / calls for k, v in op_times(profile).items()}
    scopes = dict(_META.findall(exe.as_text()))
    cumsum = sum(v for k, v in ops.items()
                 if re.search(r"cumsum|reduce_window",
                              scopes.get(k, "")))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:8]
    us = statistics.median(b - a for a, b in runs) / 1e3 if runs else None
    return {"us": us,
            "cumsum_us": cumsum,
            "top_ops_us": [[k, scopes.get(k, ""), v] for k, v in top]}


def chains(mod, cfg: dict, calls: int) -> dict:
    z = mod._sizes(cfg)
    nc, h, n = cfg["inputs"][0][0] // z["L"], z["H"], z["L"]
    rng = np.random.default_rng(0)
    steps = jnp.asarray(rng.uniform(-30.0, 0.0, (nc, h, n, 1)), jnp.float32)
    cb = jnp.asarray(rng.standard_normal((nc, 1, n, n)), jnp.float32)
    acs = jnp.asarray(rng.uniform(-30.0, 0.0, (nc, n, h)), jnp.float32)
    out, first = {}, None
    for v, cumsum in chain_variants().items():
        fn = jax.jit(segment_chain(cumsum, nc, h, n)).lower(steps, cb).compile()
        got = fn(steps, cb)
        first = got if first is None else first
        out[f"segment.{v}"] = {
            **profiled(fn, (steps, cb), calls),
            "vs_reduce_window": float(jnp.abs(got - first).max())}
        fn = jax.jit(lambda a, c=cumsum: c(a, 1)).lower(acs).compile()
        out[f"chunk.{v}"] = profiled(fn, (acs,), calls)
        del fn, got
    return out


def programs(mod, cfg: dict, calls: int) -> dict:
    import repro.api as codo
    shape = tuple(cfg["inputs"][0])
    program = codo.compile(mod.block, shape, cache=None)
    bufs = list(program.source.weights())
    keys = jax.random.split(jax.random.key(0), len(bufs) + 1)
    weights = [jax.random.normal(k, b.shape, jnp.float32)
               / math.sqrt(b.shape[0] if len(b.shape) == 2 else 1)
               for k, b in zip(keys, bufs)]
    program.bind(**{b.name: w for b, w in zip(bufs, weights)})
    x = jax.random.normal(keys[-1], shape, jnp.float32)
    ops = SimpleNamespace(mm=functools.partial(jnp.matmul, precision=HIGHEST))
    want = jax.jit(lambda w, x: mod.reference(cfg, ops, w, x))(weights, x)
    want_max = float(jnp.abs(want).max())
    env = program.make_env(x)
    graph = program.graph
    rule = lowering.lower(program.compiled, jit=True, memo=False)
    steps = lowering._build_steps(graph, rule.groups, True)
    outputs = [b.name for b in graph.outputs()]
    out = {"tri_cumsums": rule.tri_cumsums,
           "routed_kernels": len([r for g in rule.groups for r in g.routes])}
    for v, cumsum in variants().items():
        chosen = []
        for name, scope, f in steps:
            spec = graph.task(name).spec
            if spec is not None and spec.kind == "cumsum":
                head = scope.rsplit(".", 1)[0]
                kernel = "xla" if v == "reduce_window" else "tricumsum"
                (xin,), (o,) = spec.ins, spec.outs
                axis = int(spec.attrs["axis"])
                scope = f"{head}.{kernel}"
                f = (lambda env, xin=xin, o=o, axis=axis, c=cumsum:
                     {o: c(env[xin], axis)})
            chosen.append((name, scope, f))

        def run(env, chosen=chosen):
            scope = dict(env)
            for _n, label, f in chosen:
                with jax.named_scope(label):
                    scope.update(f(scope))
            return {k: scope[k] for k in outputs}

        run.__name__ = f"codo_granite_{v}"
        exe = jax.jit(run).lower(env).compile()
        got = exe(env)[outputs[0]]
        out[v] = {**profiled(exe, (env,), calls),
                  "out_err": float(jnp.abs(got - want).max()) / want_max}
        del exe, got
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-program", action="store_true",
                    help="time the layer's chains only")
    args = ap.parse_args(argv)
    mod, cfg = load_config()
    result = {"device": jax.devices()[0].device_kind, "calls": args.calls,
              "chains": chains(mod, cfg, args.calls)}
    if not args.no_program:
        result["program"] = programs(mod, cfg, args.calls)
    for part in ("chains", "program"):
        for v, r in result.get(part, {}).items():
            if isinstance(r, dict):
                print(f"{part:>8} {v:>26} {r['us'] or 0:12.2f} us  cumsum "
                      f"{r['cumsum_us']:10.2f} us  "
                      f"{r.get('out_err', r.get('vs_reduce_window'))}",
                      file=sys.stderr)
    text = json.dumps(result, indent=1)
    if args.out:
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
