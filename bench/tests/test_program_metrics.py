"""The per-layer metrics read from the program's own round records
(``repro.obs``): each reader on a hand-built history, a traced tiny cohort
run that reports them with counts equal to a hand count, and a profiler
trace that holds the program's spans as often as the records do."""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

from fedbench_testing import tiny_cell
from fedbench import harness, probes, spec, trace

from repro import obs

METRICS = ("sched_s_per_round", "assemble_s_per_round", "engine_s_per_round",
           "device_wait_s_per_round", "h2d_bytes_per_round",
           "d2h_bytes_per_round", "transfers_per_round")
SEED = 2**31 + 11
PARAMS = 44_426           # LeNet-5
LEAVES = 10
ROW_BYTES = 28 * 28 * 4 + 4   # an f32 image and its int32 label


@pytest.fixture(autouse=True)
def fresh_records():
    obs.reset()
    yield
    obs.reset()


def ticks():
    """A clock that reads 0, 1, 2, ..."""
    it = iter(range(10**6))
    return lambda: next(it)


def hand_round(scale: int) -> None:
    """One round of 15 ticks: downlink [1, 4) holding an assembly [2, 3),
    a report [5, 6), uplink [7, 10) holding a device wait [8, 9), finalize
    [11, 12), the global's encoding [13, 14)."""
    with obs.span(obs.ROUND):
        with obs.span(obs.SCHED_DOWNLINK):
            with obs.span(obs.ASSEMBLE):
                pass
        with obs.span(obs.REPORT):
            pass
        with obs.span(obs.SCHED_UPLINK):
            with obs.span(obs.WAIT):
                pass
        with obs.span(obs.FINALIZE):
            pass
        with obs.span(obs.SERVER_ENCODE):
            pass
        obs.count("h2d_bytes", 100 * scale)
        obs.count("h2d_transfers", 2 * scale)
        obs.count("d2h_bytes", 40 * scale)
        obs.count("d2h_transfers", 3 * scale)


# per round: sched 2 + 2 self; engine: round self 15 - 9, report 1,
# finalize 1, encode 1
HAND = {"sched_s_per_round": 4.0, "assemble_s_per_round": 1.0,
        "engine_s_per_round": 9.0, "device_wait_s_per_round": 1.0,
        "h2d_bytes_per_round": 100.0, "d2h_bytes_per_round": 40.0,
        "transfers_per_round": 5.0}


def test_readers_cover_the_seven_metrics():
    assert set(HAND) == set(METRICS)
    listed = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == ["cohort-q8", "cohort-f32"]


@pytest.mark.parametrize("name", METRICS)
def test_reader_on_a_hand_built_history(monkeypatch, name):
    monkeypatch.setattr(obs, "clock", ticks())
    for scale in (1, 1, 7):                 # rounds start at 0, 16, 32
        hand_round(scale)
    reader = spec.load_metric_reader(name)
    assert reader.read({"t0": 0, "t1": 32}) == pytest.approx(HAND[name])
    assert reader.read({"t0": 33, "t1": 99}) is None    # no round in it


@pytest.mark.parametrize("name", METRICS)
def test_reader_reports_nothing_without_the_recorder(monkeypatch, name):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.load_metric_reader(name).read({"t0": 0, "t1": 1}) is None


def per_client_counts(cfg: dict) -> dict:
    """What one trained client of ``cfg`` copies in a round: the installed
    global's leaves, a batch of images and labels per step and per loss
    evaluation in; two losses and the trained leaves out."""
    rows = cfg["samples_per_client"]["mean"]
    n_val = max(1, int(rows * cfg["val_fraction"]))
    n_train = rows - n_val
    steps = n_train // cfg["batch_size"]
    evaluated = min(n_train, 256) + min(n_val, 256)
    return {"h2d_bytes": PARAMS * 4 + (steps * cfg["batch_size"]
                                       + evaluated) * ROW_BYTES,
            "h2d_transfers": LEAVES + 2 * steps + 2 * 2,
            "d2h_bytes": 2 * 4 + PARAMS * 4,
            "d2h_transfers": 2 + LEAVES,
            "train_steps": steps}


def test_traced_cohort_run_reports_the_seven_metrics(monkeypatch):
    monkeypatch.setattr(harness, "peak_flops", lambda kind: 197e12)
    cell = tiny_cell("cohort-q8")
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 dropout_prob=0.0))
    result = harness.run_cell(cell, SEED, 0.01, True,
                              probes.CompileMonitor(), time.perf_counter())
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    assert set(METRICS) <= set(metrics)
    # the window's rounds after the traced one: at 0.01 s the window is
    # the tallied jobs (the reference runs none)
    window = cell.traffic["tally_jobs"] * cell.traffic["rounds_per_job"]
    rounds = obs.history()[-(window - 1):]
    trained = [r["spans"][obs.CLIENT_TRAIN]["calls"] for r in rounds]
    assert all(trained)
    one = per_client_counts(cell.config)
    assert one["train_steps"] == 1
    for r, n in zip(rounds, trained):
        assert {k: r["counters"][k] for k in one} == {
            k: n * v for k, v in one.items()}
    clients = sum(trained) / len(rounds)
    assert metrics["h2d_bytes_per_round"]["value"] == pytest.approx(
        clients * one["h2d_bytes"])
    assert metrics["d2h_bytes_per_round"]["value"] == pytest.approx(
        clients * one["d2h_bytes"])
    assert metrics["transfers_per_round"]["value"] == pytest.approx(
        clients * (one["h2d_transfers"] + one["d2h_transfers"]))
    assert metrics["sched_s_per_round"]["unit"] == "s/round"


def test_profiler_trace_holds_each_program_span(tmp_path):
    dep_cell = tiny_cell("cohort-q8")
    from fedbench.deploy import Deployment
    dep = Deployment(dep_cell, SEED)
    sim = dep.new_job(0)
    sim.run_round()                 # compiles outside the traced round
    sim = dep.new_job(1)
    harness.trace_first_round(sim, str(tmp_path), {})
    sim.run_round()
    (xplane,) = Path(tmp_path).rglob("*.xplane.pb")
    events = trace.load_events(str(xplane))
    seen: dict[str, int] = {}
    for name, _, _ in events["spans"]:
        seen[name] = seen.get(name, 0) + 1
    record = obs.history()[-1]["spans"]
    assert {n: e["calls"] for n, e in record.items()} == seen
    assert obs.ASSEMBLE in seen and obs.SCHED_UPLINK in seen
