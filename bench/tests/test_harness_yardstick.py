"""The yardstick kept with the benchmark: FLOPs from LeNet-5's shapes, the
peak table, and the whole-job window arithmetic with its tally of
``attempted`` and ``failed`` over a fixed number of jobs."""
from __future__ import annotations

import dataclasses
import json
import time
from dataclasses import dataclass, field

import numpy as np
import pytest

from fedbench_testing import BENCH, tiny_cell
from fedbench import harness, probes, spec

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


def _window(jobs: list[list[FakeResult]], wall_s: float) -> harness.Window:
    results, job_ends = [], []
    for rounds in jobs:
        results += rounds
        job_ends.append(len(results))
    return harness.Window(100.0, 100.0 + wall_s, results, job_ends)


def test_window_stats_counts_installed_folds_only():
    results = [FakeResult([0, 1, 2, 3], [0, 1, 2], dropped=[3]),
               FakeResult([0, 1, 2], [0, 1], quorum_met=False,
                          stragglers=[2]),
               FakeResult([0, 1], [0, 1])]
    s = harness.window_stats(_window([results], 6.0), tally_jobs=1)
    assert s["rounds"] == 3 and s["round_s"] == 2.0
    assert s["folded"] == 5 and s["updates_per_s"] == 5 / 6.0
    assert s["attempted"] == 9 and s["failed"] == 4
    assert s["attempted_window"] == 9 and s["failed_window"] == 4
    assert s["missed_quorum"] == 1
    assert s["dropped"] == 1 and s["stragglers"] == 1


TWO_JOBS = [
    [FakeResult([0, 1, 2, 3], [0, 1, 2], dropped=[3]),
     FakeResult([0, 1, 2, 3], [0, 1, 2, 3]),
     FakeResult([0, 1, 2], [0, 1], stragglers=[2])],
    [FakeResult([0, 1, 2, 3], [0, 1, 2, 3]),
     FakeResult([0, 1, 2, 3], [0, 1], quorum_met=False, dropped=[2, 3]),
     FakeResult([0, 1, 2, 3], [0, 1, 2, 3])],
]
# a third job failing 6 of its 12 updates, against 6 of 23 before
THIRD_JOB = [FakeResult([0, 1, 2, 3], [0, 1], dropped=[2, 3]),
             FakeResult([0, 1, 2, 3], [0, 1, 2, 3]),
             FakeResult([0, 1, 2, 3], [0], quorum_met=False,
                        dropped=[1, 2], stragglers=[3])]


@pytest.mark.parametrize("jobs, wall_s, folded", [
    (TWO_JOBS, 9.0, 17), (TWO_JOBS + [THIRD_JOB], 13.5, 23)],
    ids=["two_jobs", "three_jobs"])
def test_window_stats_tally_counts_first_jobs_only(jobs, wall_s, folded):
    """``attempted`` and ``failed`` are the first two jobs' however many
    the window holds; the rates and ``folded`` follow the whole window."""
    s = harness.window_stats(_window(jobs, wall_s), tally_jobs=2)
    assert s["attempted"] == 23 and s["failed"] == 6
    rounds = 3 * len(jobs)
    assert s["rounds"] == rounds and s["round_s"] == wall_s / rounds
    assert s["folded"] == folded and s["updates_per_s"] == folded / wall_s
    attempted = sum(len(r.participants) for job in jobs for r in job)
    assert s["attempted_window"] == attempted
    assert s["failed_window"] == attempted - folded


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
    def __init__(self, clock, tally_jobs):
        self.clock, self.jobs = clock, []
        self.traffic = {"rounds_per_job": 3, "tally_jobs": tally_jobs}

    def new_job(self, job):
        self.jobs.append(job)
        return FakeSim(self.clock, job, 1.5)


@pytest.mark.parametrize("seconds, tally_jobs, jobs", [
    (0.1, 1, 1), (4.5, 1, 1), (4.6, 1, 2), (9.0, 1, 2), (9.1, 1, 3),
    (0.1, 2, 2), (0.1, 3, 3), (9.0, 3, 3), (4.6, 2, 2), (9.1, 2, 3),
    (13.6, 2, 4)])
def test_window_closes_after_first_job_past_seconds(monkeypatch, seconds,
                                                    tally_jobs, jobs):
    """Each job takes 4.5 s; the tally holds the window open for its
    jobs, and never closes it before ``seconds``."""
    clock = FakeClock()
    monkeypatch.setattr(harness.time, "perf_counter", clock)
    dep = FakeDeployment(clock, tally_jobs)
    win = harness.run_window(dep, seconds, first_job=1)
    assert win.jobs == jobs and dep.jobs == list(range(1, jobs + 1))
    assert len(win.results) == 3 * jobs
    assert win.job_ends == [3 * j for j in range(1, jobs + 1)]
    assert win.t1 - win.t0 == pytest.approx(4.5 * jobs)


def _window_note(out: str) -> dict:
    notes = [json.loads(line) for line in out.splitlines()
             if line.startswith("{")]
    return next(n for n in notes if n.get("phase") == "window")


def test_tally_is_the_same_jobs_at_any_window_length(capsys):
    """A tiny ``cohort-f32`` with dropouts, run for its tallied jobs and
    then for more: the same ``attempted`` and ``failed``, while the
    window's own counts grow."""
    cell = tiny_cell("cohort-f32")
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 dropout_prob=0.25))
    tally = cell.traffic["tally_jobs"]

    def run(seconds):
        result = harness.run_cell(cell, 2**31 + 29, seconds, False,
                                  probes.CompileMonitor(),
                                  time.perf_counter())
        return result, _window_note(capsys.readouterr().out)

    short, short_note = run(0.01)
    assert short_note["jobs"] == tally
    long, long_note = run(4 * short_note["wall_s"])
    assert long_note["jobs"] > tally
    assert short["correct"] and long["correct"]
    assert long["attempted"] == short["attempted"]
    assert long["failed"] == short["failed"] > 0
    assert short_note["attempted_window"] == short["attempted"]
    assert long_note["attempted_window"] > long["attempted"]
