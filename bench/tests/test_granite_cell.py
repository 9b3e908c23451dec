"""granite-4.0-h-micro's period in the harness at a tiny size on the CPU:
a sound run is correct, a planted fault is not, the control fails."""

import control
import faults
import harness
import refops

CELL = "granite-h-prefill-4k"
SEED = 2**33 + 9


def test_sound_run_is_correct(checkout):
    out = harness.run_cell(checkout, CELL, SEED, 0.3, False, 0.0, chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_fault_is_not_correct(checkout):
    with faults.answer_altered():
        out = harness.run_cell(checkout, CELL, SEED, 0.3, False, 0.0,
                               chip=False)
    assert not out["correct"], out["checks"]


def test_control_fails_and_program_passes(checkout):
    harness.import_program(checkout)
    bench = harness.Bench(checkout)
    w = bench.cell(CELL)
    limits = bench.config(w["config"]).spec["limits"]["forward"]
    r = control.readings(bench, w, SEED, 0.3, lower=refops.bf16x3())
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    assert any(v > limits[k] for k, v in r["control"].items()), r
