"""The run refuses, with no result, where it cannot stand for the cell."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import REPO


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2m-fwd", "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(REPO)
    assert r.returncode == 2 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_refuses_outside_a_checkout_of_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout == ""


@pytest.mark.parametrize("switch", harness.REFUSED_ENV)
def test_refuses_with_a_routing_switch_set(switch, monkeypatch, checkout):
    monkeypatch.setenv(switch, "1")
    with pytest.raises(harness.Refused, match=switch):
        harness.run_cell(checkout, "gpt2m-fwd", 1, 0.1, False, 0.0,
                         chip=False)


def test_peaks_by_device_kind():
    bench = harness.Bench(REPO)
    peak = bench.peak("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused, match="device_kind"):
        bench.peak("TPU v99")


def test_benchmark_json_names_only_files_that_exist():
    bench = harness.Bench(REPO)
    spec = bench.spec
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()
        assert (REPO / c["file"]).with_suffix(".py").is_file()
    for w in spec["workloads"]:
        assert bench.traffic(w["traffic"])["entry"] in harness.DRIVERS
    for m in spec["per_layer"]:
        assert callable(bench.metric(m["name"]).read)
    json.dumps(spec)
