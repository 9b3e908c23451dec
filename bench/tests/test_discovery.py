"""A configuration and a traffic mix added as files under new names are
found and run without an edit to the harness."""

import json

import harness

MLP_JSON = {"source": "local test block", "d": 32, "dtype": "float32",
            "precision": "highest", "inputs": [[16, 32]],
            "limits": {"forward": {"out_err": 1e-5}}}

MLP_PY = '''
import jax


def block(x):
    from repro.core import frontend as F
    return F.add(F.fc(F.relu(F.fc(x, 2 * x.shape[1])), x.shape[1]), x)


def reference(cfg, ops, weights, x):
    w1, w2 = weights
    return ops.mm(jax.nn.relu(ops.mm(x, w1)), w2) + x
'''


def test_new_config_and_traffic_are_found(checkout):
    (checkout / "bench/configs/tiny-mlp.json").write_text(json.dumps(MLP_JSON))
    (checkout / "bench/configs/tiny-mlp.py").write_text(MLP_PY)
    (checkout / "bench/traffic/fwd-three.json").write_text(
        json.dumps({"entry": "forward", "pool": 3}))
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mlp", "source": "local",
                            "file": "bench/configs/tiny-mlp.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-mlp.fwd", "config": "tiny-mlp",
                              "traffic": "fwd-three", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("step_ms", "step_p95_ms"):
            m["workloads"].append("tiny-mlp.fwd")
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    out = harness.run_cell(checkout, "tiny-mlp.fwd", 2**40 + 3, 0.3, False,
                           0.0, chip=False)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"step_ms", "step_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
