#!/usr/bin/env python3
"""Time each XLA-lowered convolution of ResNet-18 at 224 both ways on a TPU.

    PYTHONPATH=src python3 tools/patch_conv_table.py [--calls 30] [--out F]
        [--no-variants]

For every convolution shape the lowering leaves to XLA in the
``resnet18-224`` configuration (``bench/configs/resnet18-224.py``, batch 1),
the device time of ``lax.conv_general_dilated`` and of the patch matmul
(``repro.core.lowering.patch_conv2d``), both at ``HIGHEST``:

* alone: each form jitted by itself on random data;
* inside the program: the whole lowered program with no convolution on
  the patch path, then with exactly one of C_in <= 64 on it (one variant
  each), with every 3x3 one of C_in >= 128, and with the stem and the two
  64->64 ones; the difference of the program's device time per run is the
  gain of those convolutions in the program.  Each variant compiles the
  whole program, and is profiled and freed before the next is compiled.

The variants are built here from the lowering's own step list
(``lowering._build_steps``), with the chosen convolutions' steps set
explicitly.  Then the program as ``lowering.lower`` builds it under
:func:`~repro.core.lowering.patch_conv_rule`: its device time, its
``patch_convs`` counter, its heaviest device ops, and the device time of
the ops its compiled HLO attributes to a ``g<gid>.patchconv`` scope; and
the ``patch_convs`` counter of the GPT-2-medium block.  Times are the
medians of the ``XLA Modules`` events of one profile (device clock).
Prints one JSON object; a table goes to stderr.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import re
import statistics
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.core import lowering  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST


def load_block(config: str):
    path = ROOT / "bench" / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(config.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.block


def compile_config(config: str):
    import repro.api as codo
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    shapes = [tuple(s) for s in cfg["inputs"]]
    return codo.compile(load_block(config), *shapes, cache=None), shapes


def conv_key(graph, t) -> tuple:
    """(input shape, weight shape, stride) of a ``conv2d`` task."""
    s = t.spec
    return (graph.buffers[s.ins[0]].shape, graph.buffers[s.ins[1]].shape,
            int(s.attrs.get("stride", 1)))


def xla_convs(graph, steps) -> list[str]:
    """The ungrouped ``conv2d`` tasks whose step is not a routed kernel,
    in program order."""
    out = []
    for name, scope, _f in steps:
        s = graph.task(name).spec
        if (s is not None and s.kind == "conv2d"
                and int(s.attrs.get("groups", 1)) == 1
                and scope.rsplit(".", 1)[1] in ("xla", "patchconv")):
            out.append(name)
    return out


def reads_routed(graph, groups, t) -> bool:
    """Whether ``t``'s input, through any padding tasks, is the output of
    a routed chain."""
    last = {r.tasks[-1] for g in groups for r in g.routes}
    buf = t.spec.ins[0]
    while True:
        ps = graph.producers(buf)
        if not ps:
            return False
        p = ps[0]
        if p.name in last:
            return True
        if p.spec is None or not p.spec.kind.startswith("pad"):
            return False
        buf = p.spec.ins[0]


def program_with(graph, steps, convs: list[str], patched: set[str],
                 name: str, env):
    """The program of ``steps`` with exactly the ``patched`` ones of
    ``convs`` as patch matmuls, jitted as ``codo_<name>`` and compiled for
    ``env``."""
    chosen = []
    for n, scope, f in steps:
        if n in convs:
            t = graph.task(n)
            gid = scope.split(".", 1)[0]
            scope, f = ((f"{gid}.patchconv", lowering._patch_conv_step(t.spec))
                        if n in patched else (f"{gid}.xla", t.fn))
        chosen.append((n, scope, f))
    outputs = [b.name for b in graph.outputs()]

    def program(env):
        scope = dict(env)
        for _n, label_, f in chosen:
            with jax.named_scope(label_):
                scope.update(f(scope))
        return {k: scope[k] for k in outputs}

    program.__name__ = f"codo_{name}"
    return jax.jit(program).lower(env).compile()


def flops(key) -> float:
    (n, c, h, w), (o, _c, kh, kw), s = key
    return 2.0 * n * o * ((h - kh) // s + 1) * ((w - kw) // s + 1) * c * kh * kw


def label(key) -> str:
    (_n, c, h, _w), (o, _c, kh, kw), s = key
    return f"{c}->{o} {kh}x{kw}/{s} in {h}"


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


_OP = re.compile(r"^%?([\w.\-]+) = (\w+\[[\d,]*\])")


def op_times(profile, runs: list[tuple[int, int]]) -> dict[str, float]:
    """Op ``name shape`` -> device us per run, over the ops inside ``runs``."""
    tot: dict[str, float] = defaultdict(float)
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    if any(a <= s < b for a, b in runs):
                        m = _OP.match(ev.name)
                        tot[" ".join(m.groups()) if m else ev.name[:60]] += (
                            ev.duration_ns / 1e3 / len(runs))
    return dict(tot)


_META = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"',
                   re.M)


def op_scopes(compiled) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from the compiled HLO."""
    return dict(_META.findall(compiled.as_text()))


def top(times: dict[str, float], n: int = 16) -> list:
    return sorted(([k, v] for k, v in times.items()), key=lambda kv: -kv[1])[:n]


def profile_runs(fns: dict, calls: int):
    """Warm each of ``fns`` (module name -> (callable, args)) up, then
    profile ``calls`` runs of each: the module runs and the profile."""
    for f, a in fns.values():
        jax.block_until_ready(f(*a))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for f, a in fns.values():
            for _ in range(calls):
                jax.block_until_ready(f(*a))
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        profile = jax.profiler.ProfileData.from_file(path)
    return module_runs(profile), profile


def program_runs(exe, env, calls: int):
    """The module runs of a compiled program and its op us a run."""
    runs, profile = profile_runs({"p": (exe, (env,))}, calls)
    return runs, op_times(profile, [r for rs in runs.values() for r in rs])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-variants", action="store_true",
                    help="time only the program without patch convs and "
                         "the program as the rule lowers it")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(0)

    program, shapes = compile_config("resnet18-224")
    graph = program.graph
    env = program.make_env(*[rng.standard_normal(s).astype(np.float32)
                             for s in shapes])
    graph.name, saved = "resnet_rule", graph.name
    try:
        rule = lowering.lower(program.compiled, jit=True, memo=False)
    finally:
        graph.name = saved
    steps = lowering._build_steps(graph, rule.groups, True)
    convs = xla_convs(graph, steps)
    keys = {n: conv_key(graph, graph.task(n)) for n in convs}

    fns = {}            # module name -> (callable, args)
    alone = list(dict.fromkeys(keys.values()))
    for i, key in enumerate(alone):
        x = jnp.asarray(rng.standard_normal(key[0]), jnp.float32)
        w = jnp.asarray(rng.standard_normal(key[1]), jnp.float32)
        s = key[2]

        def conv(x, w, s=s):
            return jax.lax.conv_general_dilated(
                x, w, (s, s), "VALID", dimension_numbers=("NCHW", "OIHW",
                                                          "NCHW"),
                precision=HIGHEST)

        def patch(x, w, s=s):
            return lowering.patch_conv2d(x, w, s)

        conv.__name__, patch.__name__ = f"lax_conv_{i}", f"patch_conv_{i}"
        fns[f"jit_lax_conv_{i}"] = (jax.jit(conv), (x, w))
        fns[f"jit_patch_conv_{i}"] = (jax.jit(patch), (x, w))

    variants = {"none": set()}
    if not args.no_variants:
        for n in convs:
            (_o, c, kh, kw) = keys[n][1]
            if c <= 64 and c * kh * kw >= lowering.LANES:
                variants[f"only_{n}"] = {n}
        variants["cin_128"] = {n for n in convs if keys[n][1][1] >= 128
                               and keys[n][1][2] * keys[n][1][3] > 1}
        variants["stem_pair_64"] = {n for n in convs
                                    if keys[n][1][:2] in ((64, 3), (64, 64))}

    runs, _ = profile_runs(fns, args.calls)
    programs = {}       # variant -> (module runs, op us a run)
    for v, patched in variants.items():
        exe = program_with(graph, steps, convs, patched, f"resnet_{v}", env)
        programs[v] = program_runs(exe, env, args.calls)
        del exe                     # one whole program in memory at a time
        jax.clear_caches()
    rule_exe = rule.fn.lower(env).compile()
    programs["rule"] = program_runs(rule_exe, env, args.calls)
    scopes = op_scopes(rule_exe)
    del rule_exe
    for r, _ops in programs.values():
        runs.update(r)

    def med(module: str) -> float | None:
        r = runs.get(module)
        return statistics.median(b - a for a, b in r) / 1e3 if r else None

    def gain(v: str) -> float | None:
        us = med(f"jit_codo_resnet_{v}")
        return None if None in (us, none_us) else none_us - us

    none_us = med("jit_codo_resnet_none")
    rows = []
    for n in convs:
        key = keys[n]
        i = alone.index(key)
        conv_us, patch_us = med(f"jit_lax_conv_{i}"), med(f"jit_patch_conv_{i}")
        rows.append({
            "task": n, "shape": label(key), "c_in": key[1][1],
            "reads_routed": reads_routed(graph, rule.groups, graph.task(n)),
            "gflop": flops(key) / 1e9, "conv_us": conv_us,
            "patch_us": patch_us,
            "program_gain_us": gain(f"only_{n}"),
            "conv_tflops": conv_us and flops(key) / conv_us / 1e6,
            "patch_tflops": patch_us and flops(key) / patch_us / 1e6,
            "rule": lowering.takes_patch_conv(graph, graph.task(n))})

    rule_ops = programs["rule"][1]
    patch_ops = {k: v for k, v in rule_ops.items()
                 if ".patchconv" in scopes.get(k.split()[0], "")}
    gpt2, _ = compile_config("gpt2-medium-block")
    result = {
        "device": jax.devices()[0].device_kind,
        "calls": args.calls,
        "convs": rows,
        "cin_128_gain_us": gain("cin_128"),
        "stem_pair_64_gain_us": gain("stem_pair_64"),
        "program_none_us": none_us,
        "program_rule_us": med("jit_codo_resnet_rule"),
        "patch_convs": {
            "resnet18-224": rule.patch_convs,
            "gpt2-medium-block": lowering.lower(gpt2.compiled, jit=True,
                                                memo=False).patch_convs},
        "rule_patchconv_ops_us": {k: [scopes[k.split()[0]], v]
                                  for k, v in patch_ops.items()},
        "rule_patchconv_us": sum(patch_ops.values()),
        "rule_top_ops_us": top(rule_ops),
        "none_top_ops_us": top(programs["none"][1]),
    }
    print(f"{'task':>12} {'shape':>22} {'routed in':>9} {'lax us':>8} "
          f"{'patch us':>8} {'lax TF/s':>8} {'patch TF/s':>10} "
          f"{'prog gain us':>12} rule", file=sys.stderr)
    for r in rows:
        print(f"{r['task']:>12} {r['shape']:>22} {r['reads_routed']!s:>9} "
              f"{r['conv_us'] or 0:8.2f} {r['patch_us'] or 0:8.2f} "
              f"{r['conv_tflops'] or 0:8.2f} {r['patch_tflops'] or 0:10.2f} "
              f"{r['program_gain_us'] or 0:12.2f} {r['rule']}",
              file=sys.stderr)
    print(f"C_in >= 128 gain {result['cin_128_gain_us']}, stem + 64->64 "
          f"pair gain {result['stem_pair_64_gain_us']}, program none "
          f"{none_us}, rule {result['program_rule_us']}, patch scopes "
          f"{result['rule_patchconv_us']:.2f} us", file=sys.stderr)
    text = json.dumps(result, indent=1)
    if args.out:
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
