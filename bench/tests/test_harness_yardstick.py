"""The yardstick kept with the benchmark: FLOPs from LeNet-5's shapes, the
peak table, and the whole-job window arithmetic."""
from __future__ import annotations

import json
from dataclasses import dataclass, field

import numpy as np
import pytest

from fedbench_testing import BENCH
from fedbench import harness, spec

lenet5 = spec.load_reference("lenet5")


def test_lenet5_flops_per_sample():
    assert lenet5.forward_macs_per_layer() == {
        "conv1": 86_400, "conv2": 153_600, "fc1": 30_720, "fc2": 10_080,
        "fc3": 840}
    assert lenet5.forward_flops_per_sample() == 563_280
    assert lenet5.train_flops_per_sample() == 1_517_040


def test_lenet5_reference_shapes():
    import jax

    params = lenet5.init(jax.random.key(0))
    assert lenet5.PARAM_COUNT == 44_426
    assert sum(x.size for x in jax.tree.leaves(params)) == 44_426
    logits = lenet5.forward(params, np.zeros((3, 28, 28, 1), np.float32))
    assert logits.shape == (3, 10)


def test_peaks_table():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    assert "TPU v5e" in peaks["source"]
    assert harness.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        harness.peak_flops("cpu")


@dataclass
class FakeResult:
    participants: list
    reporters: list
    quorum_met: bool = True
    dropped: list = field(default_factory=list)
    stragglers: list = field(default_factory=list)
    clock_s: float = 1.0


def test_window_stats_counts_installed_folds_only():
    results = [FakeResult([0, 1, 2, 3], [0, 1, 2], dropped=[3]),
               FakeResult([0, 1, 2], [0, 1], quorum_met=False,
                          stragglers=[2]),
               FakeResult([0, 1], [0, 1])]
    s = harness.window_stats(results, 6.0)
    assert s["rounds"] == 3 and s["round_s"] == 2.0
    assert s["folded"] == 5 and s["updates_per_s"] == 5 / 6.0
    assert s["attempted"] == 9 and s["failed"] == 4
    assert s["missed_quorum"] == 1
    assert s["dropped"] == 1 and s["stragglers"] == 1


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeSim:
    def __init__(self, clock, job, round_s):
        self.clock, self.job, self.round_s = clock, job, round_s

    def run_round(self):
        self.clock.t += self.round_s
        return FakeResult([self.job], [self.job])


class FakeDeployment:
    traffic = {"rounds_per_job": 3}

    def __init__(self, clock):
        self.clock, self.jobs = clock, []

    def new_job(self, job):
        self.jobs.append(job)
        return FakeSim(self.clock, job, 1.5)


@pytest.mark.parametrize("seconds, jobs", [(0.1, 1), (4.5, 1), (4.6, 2),
                                           (9.0, 2), (9.1, 3)])
def test_window_closes_after_first_job_past_seconds(monkeypatch, seconds,
                                                    jobs):
    clock = FakeClock()
    monkeypatch.setattr(harness.time, "perf_counter", clock)
    dep = FakeDeployment(clock)
    win = harness.run_window(dep, seconds, first_job=1)
    assert win.jobs == jobs and dep.jobs == list(range(1, jobs + 1))
    assert len(win.results) == 3 * jobs
    assert win.t1 - win.t0 == pytest.approx(4.5 * jobs)
