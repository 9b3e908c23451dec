"""The control comes out not correct: the reference one precision below the
configuration's, put in the program's place, fails at least one number of
each cell; the program itself passes all.  On the CPU, which ignores the
precision flag, the control's three bf16 passes are spelled out
(``refops.bf16x3``), and for the train step a single pass
(``refops.bf16x1``, see ``refops``); on the chip ``control.py`` reads
``HIGH`` itself."""

import pytest

import control
import harness
import refops

CELLS = ("gpt2m-fwd", "resnet18-fwd", "gpt2m-train", "gpt2m-serve")


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, checkout):
    harness.import_program(checkout)
    bench = harness.Bench(checkout)
    w = bench.cell(cell)
    limits = bench.config(w["config"]).spec["limits"][
        bench.traffic(w["traffic"])["entry"]]
    lower = refops.bf16x1() if cell == "gpt2m-train" else refops.bf16x3()
    r = control.readings(bench, w, 2**32 + 5, 0.3, lower=lower)
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    assert any(v > limits[k] for k, v in r["control"].items()), r
    if "half_batch" in r:
        assert any(v > limits[k] for k, v in r["half_batch"].items()), r
