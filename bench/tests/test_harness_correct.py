"""``correct`` at a size a CPU test run holds: a sound run of each cell
passes its limits, and the control (the reference in bfloat16 put in the
program's place) does not."""
from __future__ import annotations

import time

import pytest

from fedbench_testing import tiny_cell
from fedbench import check, harness, probes

import calibrate

CELLS = ("cohort-q8", "paper-f32", "cohort-f32")
SEED = 2**31 + 11


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = tiny_cell(name)
    result = harness.run_cell(cell, SEED, 0.01, False,
                              probes.CompileMonitor(), time.perf_counter())
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(check.NAMES)
    assert {m["name"] for m in cell.end_to_end} == set(result["metrics"])
    assert result["attempted"] >= result["failed"] >= 0


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    monkeypatch.setattr(harness, "peak_flops", lambda kind: 197e12)
    cell = tiny_cell("paper-f32")
    result = harness.run_cell(cell, SEED, 0.01, True,
                              probes.CompileMonitor(), time.perf_counter())
    assert result["correct"], result["checks"]
    # no TPU plane in a CPU trace: the device readers find nothing
    assert set(result["metrics"]) == {
        "train_s_per_round", "encode_s_per_round", "fold_s_per_round",
        "transport_s_per_round", "mfu"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    got = dict(calibrate.readings_for(cell, SEED))
    assert check.judge(got["program"], cell.limits)[0], got["program"]
    ok, shown = check.judge(got["control"], cell.limits)
    assert not ok, shown
