"""CPU tests of the benchmark harness: tiny copies of the configurations in
a scratch checkout, the program's Pallas kernels on their jnp paths."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

# Tiny sizes of each configuration: the keys that differ from the real file.
TINY = {
    "gpt2-medium-block": {"n_embd": 64, "n_ctx": 128, "n_positions": 128,
                          "inputs": [[128, 64]],
                          "train_inputs": [[128, 64], [128, 64]]},
    "resnet18-224": {"image_size": 32, "inputs": [[1, 3, 32, 32]]},
}


# The served cell is out of BENCHMARK.json (its tail spread too widely for a
# bound; PERF.md), but its driver, mix and readers stay, so the tests add
# it back to the scratch checkout: a later benchmark PR re-adds it as data.
SERVE_CELL = {
    "workload": {"name": "gpt2m-serve", "config": "gpt2-medium-block",
                 "traffic": "serve-poisson", "chips": 1, "why": "served"},
    "end_to_end": [{"name": "request_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["gpt2m-serve"]}],
    "per_layer": [{"name": n, "unit": u, "better": b, "source": src,
                   "layer": layer, "moves": "request_p95_ms",
                   "workloads": ["gpt2m-serve"]}
                  for n, u, b, src, layer in (
                      ("device_idle_share.serve", "%", "lower",
                       "device_trace", "device"),
                      ("mean_batch.serve", "requests", "higher",
                       "program_counter", "serving runtime"))],
}


def make_checkout(dst: Path, traffic: dict | None = None) -> Path:
    """A checkout at ``dst``: the benchmark's files with tiny configs and
    the served cell, the program's ``src`` beside them, and ``traffic``
    overrides per mix."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (dst / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = dst / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(TINY[c["name"]])
        path.write_text(json.dumps(cfg))
    for name, over in (traffic or {}).items():
        path = dst / "bench" / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t.update(over)
        path.write_text(json.dumps(t))
    spec["workloads"].append(SERVE_CELL["workload"])
    spec["end_to_end"] += SERVE_CELL["end_to_end"]
    spec["per_layer"] += SERVE_CELL["per_layer"]
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(tmp_path)
