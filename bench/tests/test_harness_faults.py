"""Each fault a cell can have, planted underneath the timed path, turns a
whole run's ``correct`` false (the harness's look for a chip skipped)."""
from __future__ import annotations

import time

import pytest

from fedbench_testing import tiny_cell
from fedbench import faults, harness, probes

SEED = 2**31 + 23


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("name", ("cohort-q8", "paper-f32"))
def test_fault_makes_run_incorrect(name, fault):
    cell = tiny_cell(name)
    with faults.planted(fault):
        result = harness.run_cell(cell, SEED, 0.01, False,
                                  probes.CompileMonitor(),
                                  time.perf_counter())
    assert not result["correct"], result["checks"]


def test_faults_are_undone():
    from repro.fl import aggregation, client, server

    before = (server.FLServer.finalize_aggregation,
              aggregation.RunningFedAvg.add, client.chunk_stream)
    for fault in faults.FAULTS:
        with faults.planted(fault):
            pass
    assert before == (server.FLServer.finalize_aggregation,
                      aggregation.RunningFedAvg.add, client.chunk_stream)
