"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the sizes as run, the stated dtype and
  precision, the limits of the correctness check; beside it
  ``configs/<config>.py`` — the model source in CODO's frontend ops
  (``block``, and ``loss`` where it trains) and its plain reference;
* ``traffic/<mix>.json`` — the parameters one general driver reads:
  ``entry`` (``forward``, ``train`` or ``serve``), the pool of distinct
  inputs, and for ``serve`` the arrival rate and batching;
* ``metrics/<metric>.py`` — ``read(ctx)`` returns the metric's value from
  the traced run, or ``None`` where it finds nothing to read.

A run: refuse without a TPU (``run.py`` checks), make weights and inputs on
the device from the seed, compile through ``codo.compile(..., cache=None)``
so that the passes run in every run, warm up the cell's shapes, measure for
``seconds``, then compare what the window produced with the reference.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

import refops
import stats
import trace as trace_mod
import work

# Switches that change which kernels run or how the gate prices them;
# routing here must come from committed code alone.
REFUSED_ENV = ("CODO_PALLAS_INTERPRET", "CODO_FORCE_PALLAS",
               "CODO_DISABLE_PALLAS", "CODO_BACKEND", "CODO_TUNING_DB",
               "CODO_ROUTING_CALIBRATION")
SAMPLE = 8              # outputs of the window kept for the check
TRACE_SECONDS = 2.0     # length of the profiled window of a --trace 1 run
LATE_WAIT_S = 60.0      # how long past the window's close answers may come


class Refused(RuntimeError):
    """The run cannot stand for the cell: no result is printed."""


# --------------------------------------------------------------------------
# Finding the pieces by name
# --------------------------------------------------------------------------


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Config:
    name: str
    spec: dict          # configs/<name>.json
    model: object       # configs/<name>.py


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.here = self.root / self.spec["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise Refused(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Config:
        for c in self.spec["configs"]:
            if c["name"] == name:
                path = self.root / c["file"]
                return Config(name, json.loads(path.read_text()),
                              load_module(path.with_suffix(".py")))
        raise Refused(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json").read_text())

    def metric(self, name: str):
        return load_module(self.here / "metrics" / f"{name}.py")

    def metrics_of(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def peak(self, device_kind: str) -> dict:
        peaks = json.loads((self.here / "peaks.json").read_text())
        if device_kind not in peaks:
            raise Refused(f"no peaks for device_kind {device_kind!r} in "
                          f"peaks.json (known: {sorted(peaks)})")
        return peaks[device_kind]


# --------------------------------------------------------------------------
# Device, environment, seeds
# --------------------------------------------------------------------------


def check_environment() -> None:
    set_env = [k for k in REFUSED_ENV if os.environ.get(k)]
    if set_env:
        raise Refused(f"refusing to run with {set_env} set: routing must "
                      f"come from committed code alone")


def check_device(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0].platform is "
                      f"{devs[0].platform!r}; this benchmark has no CPU "
                      f"fallback")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, jax sees {len(devs)}")
    return devs


def import_program(root: Path) -> None:
    """Put the program on the path, and JAX's persistent compilation cache
    at the fixed ``<checkout>/.jax_cache`` (the program's own helper takes
    the directory from ``JAX_COMPILATION_CACHE_DIR``)."""
    cache = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(Path(root) / "src"))
    try:
        import repro.api  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program (src/repro) is not in {root}: {e}") from e
    from repro.core.tuning import default_tuning_db
    n = len(default_tuning_db().entries)
    if n:
        raise Refused(f"the tuning database has {n} entries: routing must "
                      f"come from committed code alone")


def prng_key(seed: int, stream: int):
    """A threefry key for ``stream`` of ``seed``; any whole ``seed``."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


W_STREAM, X_STREAM, SAMPLE_STREAM, ARRIVAL_STREAM = 1, 2, 3, 4


def normal_arrays(key, shapes, *, fan_in: bool):
    """One jitted call: N(0, 1) arrays of ``shapes``, or N(0, 1/fan_in)
    (rank 2: rows; rank 4: in-channels x window) for weights."""
    shapes = tuple(tuple(int(d) for d in s) for s in shapes)

    def scale(s):
        if not fan_in:
            return 1.0
        return 1.0 / math.sqrt(s[0] if len(s) == 2 else math.prod(s[1:]))

    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(shapes))
        return [jax.random.normal(ki, s, jnp.float32) * scale(s)
                for ki, s in zip(ks, shapes)]
    return make(key)


def bind_weights(program, seed: int) -> list:
    """Weights of ``program``'s source, in creation order, made on the
    device from ``seed`` and bound by name; returns them."""
    bufs = list(program.source.weights())
    weights = normal_arrays(prng_key(seed, W_STREAM),
                            [b.shape for b in bufs], fan_in=True)
    program.bind(**{b.name: w for b, w in zip(bufs, weights)})
    return weights


def input_pool(seed: int, shapes, pool: int) -> list:
    """``pool`` distinct argument lists of ``shapes``, from ``seed``."""
    flat = normal_arrays(prng_key(seed, X_STREAM), list(shapes) * pool,
                         fan_in=False)
    n = len(shapes)
    return [flat[i * n:(i + 1) * n] for i in range(pool)]


class CompileEvents:
    """Counts JAX's compile requests and persistent-cache hits and misses."""

    NAMES = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        self.counts: Counter = Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    def snapshot(self) -> Counter:
        return Counter(self.counts)


class Reservoir:
    """A uniform sample of ``k`` items of a stream, drawn from the seed."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item) -> None:
        if self.seen < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def rel_err(got, want) -> float:
    """Largest absolute gap over the reference's largest magnitude; inf
    for a wrong shape or a non-finite value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return math.inf
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def reference_fn(cfg: Config, fn_name: str, ops):
    """The config's reference function with its contractions at ``ops``."""
    fn = getattr(cfg.model, fn_name)
    return jax.jit(lambda *a: fn(cfg.spec, ops, *a))


def routing(lowered) -> list:
    """(kernel, task names) of every route in a lowered program."""
    return [(r.kernel, list(r.tasks)) for g in lowered.groups
            for r in g.routes]


def route_work(program) -> list:
    """(kernel, flops, bytes, out elements) of every route of a program."""
    g = program.graph
    return [(k, *work.chain_work(g, t), work.chain_out_elems(g, t))
            for k, t in routing(program.lower(jit=True))]


# --------------------------------------------------------------------------
# Drivers, one per entry point: set-up, one step or the open loop, check
# --------------------------------------------------------------------------


@dataclass
class Setup:
    passes_s: float = 0.0
    lower_compile_s: float = 0.0
    routes: list = field(default_factory=list)  # route_work() of each program
    flops_per_step: float = 0.0


class Forward:
    """Back-to-back ``CompiledProgram.__call__`` on a pool of inputs."""

    span = "bench.forward"

    def __init__(self, cfg: Config, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def setup(self) -> Setup:
        import repro.api as codo
        shapes = [tuple(s) for s in self.cfg.spec["inputs"]]
        program = codo.compile(self.cfg.model.block, *shapes, cache=None)
        out = Setup(passes_s=sum(r.seconds for r in
                                 program.diagnostics.records))
        self.weights = bind_weights(program, self.seed)
        self.xs = input_pool(self.seed, shapes, int(self.traffic["pool"]))
        t0 = time.perf_counter()
        program.lower(jit=True)
        jax.block_until_ready(program(*self.xs[0]))
        out.lower_compile_s = time.perf_counter() - t0
        out.routes = route_work(program)
        out.flops_per_step = work.graph_flops(program.source)
        self.program = program
        self.source = program.source
        return out

    def step(self, i: int):
        slot = i % len(self.xs)
        with _span(self.span):
            out = self.program(*self.xs[slot])
        with _span("bench.wait"):
            jax.block_until_ready(out)
        return slot, out

    def free(self) -> None:
        del self.program

    def check(self, sample: list, ops=None) -> dict:
        ops = ops or refops.at(self.cfg.spec["precision"])
        ref = reference_fn(self.cfg, "reference", ops)
        err = max(rel_err(out, ref(self.weights, *self.xs[slot]))
                  for slot, out in sample)
        return {"out_err": err}

    def plain_xla_ms(self, n: int) -> float:
        """Per-call time of ``jax.jit(source.execute)`` on the same
        environment: the plain-XLA program of the same source."""
        env = self.program.make_env(*self.xs[0])
        plain = jax.jit(self.source.execute)
        jax.block_until_ready(plain(env))
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(plain(env))
        return (time.perf_counter() - t0) / n * 1e3


class Train:
    """Back-to-back ``CompiledTrainStep.step`` with params and optimizer
    state carried forward.  Set-up drives the first ``CHECKED`` steps
    through the same call on distinct rows; the reference follows them."""

    span = "bench.train_step"
    CHECKED = 3

    def __init__(self, cfg: Config, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def setup(self) -> Setup:
        import repro.api as codo
        shapes = [tuple(s) for s in self.cfg.spec["train_inputs"]]
        step = codo.compile(self.cfg.model.loss, *shapes, grad=True,
                            opt=self.cfg.spec["optimizer"], cache=None)
        out = Setup(passes_s=sum(r.seconds for p in (step.forward,
                                                     step.backward,
                                                     step.update)
                                 for r in p.diagnostics.records))
        wbufs = [b for b in step.source.weights()
                 if b.name in set(step.param_names)]
        self.names = [b.name for b in wbufs]
        self.p0 = normal_arrays(prng_key(self.seed, W_STREAM),
                                [b.shape for b in wbufs], fan_in=True)
        self.xs = input_pool(self.seed, shapes, int(self.traffic["pool"]))
        zeros = [jnp.zeros_like(p) for p in self.p0]
        self.params = dict(zip(self.names, self.p0))
        self.opt = {"m": dict(zip(self.names, zeros)),
                    "v": dict(zip(self.names, zeros)),
                    "step": np.zeros((), np.int32)}
        self.program, self.first = step, 0
        t0 = time.perf_counter()
        for p in (step.forward, step.backward, step.update):
            p.lower(jit=True)
        self.losses = [self.step(0)[1]]
        out.lower_compile_s = time.perf_counter() - t0
        self.m1 = [self.opt["m"][n] for n in self.names]
        for i in range(1, self.CHECKED):
            self.losses.append(self.step(i)[1])
        self.p3 = [self.params[n] for n in self.names]
        out.routes = [r for p in (step.forward, step.backward, step.update)
                      for r in route_work(p)]
        # Forward and backward of the loss graph: the backward does twice
        # the forward's contractions; recomputation does not count.
        out.flops_per_step = 3 * work.graph_flops(step.source)
        self.first = self.CHECKED
        return out

    def step(self, i: int):
        x = self.xs[(i + self.first) % len(self.xs)]
        with _span(self.span):
            self.params, self.opt, metrics = self.program.step(
                self.params, self.opt, *x)
        with _span("bench.wait"):
            jax.block_until_ready(self.params)
        return i, float(metrics["loss"])

    def free(self) -> None:
        del self.program, self.params, self.opt

    def reference_steps(self, ops) -> tuple[list, list, list, list]:
        """The reference's first ``CHECKED`` steps from the same weights and
        rows: (losses, first moments after step 1, params after the last
        step, raw gradients of step 1)."""
        vg = jax.jit(jax.value_and_grad(
            lambda ps, x, t: self.cfg.model.reference_loss(
                self.cfg.spec, ops, ps, x, t)))
        adamw = jax.jit(lambda p, g, m, v, s: self.cfg.model.adamw(
            self.cfg.spec, p, g, m, v, s), static_argnums=4)
        p = list(self.p0)
        m = [jnp.zeros_like(a) for a in p]
        v = [jnp.zeros_like(a) for a in p]
        losses = []
        for i in range(self.CHECKED):
            loss, g = vg(p, *self.xs[i])
            losses.append(float(loss))
            p, m, v = adamw(p, g, m, v, i)
            if i == 0:
                m1, g1 = m, g
        return losses, m1, p, g1

    def check(self, sample: list, ops=None) -> dict:
        losses, m1, p3, g1 = self.reference_steps(
            ops or refops.at(self.cfg.spec["precision"]))
        b1 = self.cfg.spec["optimizer"]["b1"]
        got_g = [np.asarray(a) / (1 - b1) for a in self.m1]
        want_g = [np.asarray(a) / (1 - b1) for a in m1]
        # Leaves whose gradient is nought to rounding move by round-off
        # alone under Adam: they stay out of the change's comparison.
        gnorm = [float(np.linalg.norm(np.asarray(a))) for a in g1]
        keep = [n >= 1e-3 * float(np.median(gnorm)) for n in gnorm]
        p0 = [np.asarray(a) for a in self.p0]
        got_d = [np.asarray(a) - b for a, b, k in zip(self.p3, p0, keep) if k]
        want_d = [np.asarray(a) - b for a, b, k in zip(p3, p0, keep) if k]
        return {
            "loss_gap": max(_gap(a, b) for a, b in zip(self.losses, losses)),
            "grad_gap": _leaf_gap(got_g, want_g),
            "update_gap": _leaf_gap(got_d, want_d),
        }


def _gap(got: float, want: float) -> float:
    if not math.isfinite(got):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def _leaf_gap(got: list, want: list) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    gn = [float(np.linalg.norm(a)) for a in got]
    wn = [float(np.linalg.norm(b)) for b in want]
    med = float(np.median(wn))
    if not all(math.isfinite(g) for g in gn):
        return math.inf
    return max(abs(g - w) / max(w, med, 1e-30) for g, w in zip(gn, wn))


class Serve:
    """Open-loop Poisson arrivals of single host arrays into an in-process
    ``ServingRuntime``, each request timed from when it was due."""

    span = "bench.submit"

    def __init__(self, cfg: Config, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def setup(self) -> Setup:
        import repro.api as codo
        from repro.core.cache import CompileCache
        from repro.serving.runtime import ServeConfig, ServingRuntime
        shape = tuple(self.cfg.spec["inputs"][0])
        program = codo.compile(self.cfg.model.block, shape, cache=None)
        self.weights = bind_weights(program, self.seed)
        self.xs = [np.asarray(x) for x, in input_pool(
            self.seed, [shape], int(self.traffic["pool"]))]
        self.input = program.input_names[0]
        self.output = program.output_names[0]
        t = self.traffic
        self.rt = ServingRuntime(
            ServeConfig(workers=0, max_batch=int(t["max_batch"]),
                        batch_window_ms=float(t["batch_window_ms"])),
            cache=CompileCache())
        t0 = time.perf_counter()
        self.handle = self.rt.add_model("m", program)       # warms size 1
        for size in range(2, int(t["max_batch"]) + 1):
            self.rt.pause()
            futs = [self.rt.submit("m", **{self.input: self.xs[i]})
                    for i in range(size)]
            self.rt.resume()
            for f in futs:
                f.result(timeout=600)
        out = Setup(lower_compile_s=time.perf_counter() - t0)
        progs = [program, *self.handle.batched.values()]
        out.passes_s = sum(r.seconds for p in progs
                           for r in p.diagnostics.records)
        out.routes = [r for p in progs for r in route_work(p)]
        out.flops_per_step = work.graph_flops(program.source)
        self.stats0 = self.rt.stats.snapshot()
        return out

    def run(self, seconds: float):
        """The open loop: returns (latencies in s or inf for a request that
        failed or never came, sampled (slot, output), generator lateness)."""
        rate = float(self.traffic["rate_per_s"])
        rng = host_rng(self.seed, ARRIVAL_STREAM)
        n = int(seconds * rate * 2) + 16
        due = np.cumsum(rng.exponential(1.0 / rate, n))
        due = due[due < seconds]
        slots = rng.integers(0, len(self.xs), len(due))
        sample = Reservoir(SAMPLE, host_rng(self.seed, SAMPLE_STREAM))
        for i in range(len(due)):
            sample.offer(i)
        keep = set(sample.items)
        done: dict[int, float] = {}
        results: dict[int, object] = {}
        todo: queue.Queue = queue.Queue()

        def watch():
            while True:
                item = todo.get()
                if item is None:
                    return
                i, fut, deadline = item
                if fut.wait(max(0.0, deadline - time.perf_counter())):
                    try:
                        out = fut.result(0)
                    except Exception:           # noqa: BLE001 — a failed request
                        continue
                    done[i] = time.perf_counter()
                    if i in keep:
                        results[i] = out[self.output]

        watcher = threading.Thread(target=watch, name="bench-watch")
        watcher.start()
        late = []
        t0 = time.perf_counter()
        deadline = t0 + seconds + LATE_WAIT_S
        try:
            for i, (d, slot) in enumerate(zip(due, slots)):
                wait = t0 + d - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - (t0 + d))
                with _span(self.span):
                    try:
                        fut = self.rt.submit("m", **{self.input:
                                                     self.xs[slot]})
                    except Exception:           # noqa: BLE001 — queue full
                        continue
                todo.put((i, fut, deadline))
        finally:
            todo.put(None)
            watcher.join()
        self.window_s = time.perf_counter() - t0
        lat = [done[i] - (t0 + d) if i in done else math.inf
               for i, d in enumerate(due)]
        sampled = [(int(slots[i]), results.get(i)) for i in sorted(keep)]
        return lat, sampled, late

    def batches(self) -> tuple[int, int]:
        s = self.rt.stats.snapshot()
        reqs = (s["batched_requests"] + s["fallback_requests"]
                - self.stats0["batched_requests"]
                - self.stats0["fallback_requests"])
        return s["batches"] - self.stats0["batches"], reqs

    def free(self) -> None:
        self.rt.close()
        del self.rt, self.handle

    def check(self, sample: list, ops=None) -> dict:
        ops = ops or refops.at(self.cfg.spec["precision"])
        ref = reference_fn(self.cfg, "reference", ops)
        err = max(math.inf if out is None else
                  rel_err(out, ref(self.weights, self.xs[slot]))
                  for slot, out in sample)
        return {"out_err": err}


DRIVERS = {"forward": Forward, "train": Train, "serve": Serve}


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------


def closed_loop(driver, seconds: float, seed: int):
    """Back-to-back steps for ``seconds``: (step times, sampled outputs,
    attempted, failed, window length)."""
    sample = Reservoir(SAMPLE, host_rng(seed, SAMPLE_STREAM))
    times, failed = [], 0
    t0 = time.perf_counter()
    end, i = t0 + seconds, 0
    while time.perf_counter() < end:
        t = time.perf_counter()
        try:
            key, out = driver.step(i)
        except Exception as e:                  # noqa: BLE001 — counted
            print(f"step {i} raised {type(e).__name__}: {e}", file=sys.stderr)
            failed += 1
        else:
            if isinstance(out, float) and not math.isfinite(out):
                failed += 1
            sample.offer((key, out))
        times.append(time.perf_counter() - t)
        i += 1
    return times, sample.items, i, failed, time.perf_counter() - t0


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, chip: bool = True) -> dict:
    """One run of ``workload``; returns the result line's object.  With
    ``chip=False`` the look for a TPU is skipped (the tests' CPU runs)."""
    log = sys.stderr
    check_environment()
    bench = Bench(root)
    cell = bench.cell(workload)
    devs = check_device(int(cell["chips"])) if chip else jax.devices()
    dev = devs[0]
    peak = bench.peak(dev.device_kind) if chip else None
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devs)}, "
          f"jax {jax.__version__}", file=log)
    import_program(root)
    events = CompileEvents()
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    entry = traffic["entry"]
    driver = DRIVERS[entry](cfg, traffic, seed)

    c0 = events.snapshot()
    setup = driver.setup()
    c1 = events.snapshot()
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f} s: passes {setup.passes_s:.3f} s, lower + "
          f"compile {setup.lower_compile_s:.3f} s; persistent cache "
          f"{c1['hits'] - c0['hits']} hits, {c1['misses'] - c0['misses']} "
          f"misses; {len(setup.routes)} routed kernel(s): "
          f"{[r[0] for r in setup.routes]}", file=log)

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    try:
        if entry == "serve":
            lat, sample, late = driver.run(window)
            attempted = len(lat)
            failed = sum(1 for x in lat if not math.isfinite(x))
            window_s = driver.window_s
            print(f"generator lateness: p50 {stats.percentile(late, .5) * 1e3:.3f}"
                  f" ms, p95 {stats.percentile(late, .95) * 1e3:.3f} ms, "
                  f"max {max(late) * 1e3:.3f} ms over {len(late)} requests"
                  if late else "generator: no request was due", file=log)
        else:
            times, sample, attempted, failed, window_s = closed_loop(
                driver, window, seed)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c2 = events.snapshot()
    print(f"compiles inside the window: {c2['requests'] - c1['requests']}",
          file=log)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    metrics, device, breakdown = {}, {}, None
    if trace:
        try:
            red = trace_mod.reduce_dir(tdir, chips=1)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device = {"busy_s": red.busy_s, "window_s": red.window_s}
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
        calls = trace_mod.kernel_calls("\n".join({n for _, _, n in red.ops[0]}))
        for kernel, flops, nbytes, _ in setup.routes:
            least, bound = work.least_seconds(flops, nbytes, peak)
            print(f"route {kernel}: {flops} flops, {nbytes} bytes, least "
                  f"{least * 1e6:.3f} us, bound by {bound}", file=log)
        ctx = {"trace": red, "setup": setup, "peak": peak, "calls": calls,
               "steps": attempted, "entry": entry}
        if entry == "serve":
            ctx["batches"], ctx["requests"] = driver.batches()
        if entry == "forward":
            xla = driver.plain_xla_ms(50)
            print(f"plain XLA (jax.jit(source.execute)): {xla:.4f} ms per "
                  f"call; compiled program {window_s / attempted * 1e3:.4f} "
                  f"ms per step (traced window)", file=log)
        for m in bench.metrics_of("per_layer", workload):
            value = bench.metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reported = {m["name"]: m for m in bench.metrics_of("end_to_end",
                                                           workload)}
        values = {"setup_s": setup_s}
        if entry == "serve":
            values["request_p95_ms"] = stats.percentile(lat, .95) * 1e3
        else:
            values["step_ms"] = window_s / attempted * 1e3
            values["step_p95_ms"] = stats.percentile(times, .95) * 1e3
        for name, m in reported.items():
            v = values[name]            # inf: a request failed or never came
            metrics[name] = {"value": v if math.isfinite(v) else None,
                             "unit": m["unit"]}

    driver.free()
    limits = cfg.spec["limits"][entry]
    checks = driver.check(sample)
    correct = (attempted > 0 and failed == 0 and all(
        math.isfinite(v) and v <= limits[k] for k, v in checks.items()))
    for k, v in checks.items():
        print(f"check {k}: {v!r} (limit {limits[k]!r})", file=log)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs), "memory_peak_bytes": int(mem),
                      **device}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v if math.isfinite(v) else None,
                         "limit": limits[k]} for k, v in checks.items()}
    return out
