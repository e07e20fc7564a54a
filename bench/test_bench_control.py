"""The control comes out not correct, at a size a test run can hold.

The control is the program's own lower-precision path, one bfloat16 pass of
the coupling and input products (`ExecPlan(precision="mixed")`): a run of
either cell with it switched on fails the cell's limits, the same run at
the configuration's precision passes them. The reservoir keeps the cell's
width and hold window; only the slot count, the window and the session
length are cut (PERF.md, section 2, gives the chip readings)."""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import harness, registry  # noqa: E402


# cell -> (slots, session ticks): whole sessions at N=1, the cell's own
# horizon or less at N=1000
SIZES = {"n1.readout_closed": (32, 64), "n1000.readout_closed": (8, 16)}


def run(name, precision, seed):
    slots, ticks = SIZES[name]
    cell = registry.Cell(name)
    cell.config["plan"].update({"ensemble": slots, "precision": precision})
    cell.config["readout"]["pool"] = slots
    cell.traffic["session_ticks"] = ticks
    cell.check.update({"sample_sessions": slots, "reference_block": slots,
                       "horizon_ticks": min(ticks, cell.check["horizon_ticks"])})
    # rehearse=False: the program's plain CPU path (Pallas not interpreted),
    # which serves the hold window fast enough for a test
    return harness.execute(cell, seed, 2.0, False, False, time.perf_counter())


@pytest.mark.parametrize("name", sorted(SIZES))
def test_configured_precision_passes(name):
    res = run(name, None, 3000000043)
    assert len(res.sample) > 0
    assert res.line["correct"], res.checks


@pytest.mark.parametrize("name", sorted(SIZES))
def test_bf16_coupling_control_fails(name):
    res = run(name, "mixed", 3000000043)
    assert len(res.sample) > 0
    assert not res.line["correct"], res.checks
