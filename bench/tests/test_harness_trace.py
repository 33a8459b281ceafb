"""The trace reduction: busy union, idle share, top operations and idle
time named by the host span open during it."""
from __future__ import annotations

import json

import pytest

from fedbench_testing import BENCH
from fedbench import trace

RECORDED = BENCH / "tests" / "data" / "trace_paper_round.json"

EVENTS = {
    "devices": {"/device:TPU:0": [["A", 10, 10], ["B", 15, 15],
                                  ["A", 50, 10], ["C", 90, 20]]},
    "spans": [["fl.job", 0, 100], ["fl.round", 5, 90], ["fl.train", 25, 30],
              ["fl.fold", 70, 10], ["fl.round", 200, 10]],
}


def test_union_and_gaps():
    busy = trace.union([(10, 20), (15, 30), (50, 60), (90, 110)], 0, 100)
    assert busy == [(10, 30), (50, 60), (90, 100)]
    assert trace.gaps(busy, 0, 100) == [(0, 10), (30, 50), (60, 90)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_innermost_segments():
    segs = trace.innermost(EVENTS["spans"][:4])
    assert segs == [(0, 5, "fl.job"), (5, 25, "fl.round"),
                    (25, 55, "fl.train"), (55, 70, "fl.round"),
                    (70, 80, "fl.fold"), (80, 95, "fl.round"),
                    (95, 100, "fl.job")]


def test_reduce_by_hand():
    got = trace.reduce(EVENTS, 0, 100)
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["devices"] == 1
    assert [k for k, _ in got["device_ops"]] == ["A", "B", "C"]
    assert [v for _, v in got["device_ops"]] == pytest.approx(
        [20e-9, 15e-9, 10e-9])
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"fl.round": 25e-9, "fl.train": 20e-9, "fl.fold": 10e-9,
         "fl.job": 5e-9})


def test_idle_outside_spans_is_named_none():
    ev = {"devices": {"/device:TPU:0": [["A", 40, 20]]},
          "spans": [["fl.train", 10, 20]]}
    got = dict(trace.reduce(ev, 0, 100)["idle_gaps"])
    assert got == pytest.approx({"none": 60e-9, "fl.train": 20e-9})


def test_busy_is_averaged_over_devices():
    ev = {"devices": {"/device:TPU:0": [["A", 0, 50]],
                      "/device:TPU:1": [["A", 0, 100]]},
          "spans": []}
    got = trace.reduce(ev, 0, 100)
    assert got["busy_s"] == pytest.approx(75e-9) and got["devices"] == 2


def _sweep_busy(ops, lo, hi):
    """Busy time by counting open operations at every boundary."""
    marks = sorted([(max(s, lo), 1) for _, s, d in ops if s + d > lo and
                    s < hi] + [(min(s + d, hi), -1) for _, s, d in ops
                               if s + d > lo and s < hi])
    busy, depth, last = 0, 0, lo
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_chip_trace():
    events = json.loads(RECORDED.read_text())
    lo, hi = trace.first_span(events, "fl.round")
    got = trace.reduce(events, lo, hi)
    (ops,) = events["devices"].values()
    assert got["busy_s"] == pytest.approx(_sweep_busy(ops, lo, hi) / 1e9)
    assert 0 < got["busy_s"] < got["window_s"]
    busy = trace.union([(s, s + d) for _, s, d in ops], lo, hi)
    named = trace.attribute(trace.gaps(busy, lo, hi), trace.innermost(
        [s for s in events["spans"] if s[1] < hi and s[1] + s[2] > lo]))
    assert sum(named.values()) / 1e9 + got["busy_s"] == pytest.approx(
        got["window_s"])
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    totals = {}
    for name, s, d in ops:
        ov = min(s + d, hi) - max(s, lo)
        if ov > 0:
            totals[name] = totals.get(name, 0) + ov / 1e9
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    assert [k for k, _ in got["device_ops"]] == [k for k, _ in top]
