"""A run with the timed path broken underneath comes out not correct; the
same run unbroken comes out correct.  The look for a chip is skipped and
the rest of the run is driven as on the chip, at tiny sizes."""

import pytest

import faults
import harness

SEED = 2**31 + 17
CELLS = ("gpt2m-fwd", "resnet18-fwd", "gpt2m-train", "gpt2m-serve")
FAULTS = [("gpt2m-fwd", faults.answer_altered),
          ("resnet18-fwd", faults.answer_altered),
          ("gpt2m-serve", faults.served_answer_altered),
          ("gpt2m-train", faults.state_unchanged),
          ("gpt2m-train", faults.half_batch)]


def _run(checkout, cell):
    return harness.run_cell(checkout, cell, SEED, 0.3, False, 0.0, chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, checkout):
    out = _run(checkout, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, checkout):
    with fault():
        out = _run(checkout, cell)
    assert not out["correct"], out["checks"]
