"""BENCHMARK.json names files that exist and load, within the contract's
limits, and a new cell, mix, config or metric is found by name."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from fedbench_testing import BENCH, ROOT
from fedbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = spec.load_benchmark(ROOT)


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert (ROOT / BENCHMARK["command"][1]).is_file()


@pytest.mark.parametrize("cfg", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_file_loads(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and cfg["file"].startswith("bench/")
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    assert all(k in data for k in cfg["reduced"])
    spec.load_reference(data["model"])


@pytest.mark.parametrize("wl", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_workload_cell_loads(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4) and len(wl["why"]) <= 200
    cell = spec.load_cell(wl["name"], ROOT)
    assert cell.traffic["name"] == wl["traffic"]
    assert cell.config["name"] == wl["config"]
    assert {m["name"] for m in cell.end_to_end} >= {"round_s", "setup_s"}
    assert cell.per_layer and set(cell.limits) >= {"fold_mismatch"}


@pytest.mark.parametrize("path", sorted((BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_traffic_file_tallies_whole_jobs(path):
    """Every mix names the jobs its ``attempted`` and ``failed`` count:
    a whole number, at least 2 fresh jobs after the checked one."""
    traffic = json.loads(path.read_text())
    assert traffic["name"] == path.stem
    tally = traffic["tally_jobs"]
    assert isinstance(tally, int) and not isinstance(tally, bool)
    assert tally >= 2


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_reader_loads(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert callable(spec.load_metric_reader(metric["name"]).read)


def test_end_to_end_bounds():
    names = [m["name"] for m in BENCHMARK["end_to_end"]]
    assert "setup_s" in names and len(names) == len(set(names))
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_new_files_are_found_by_name(tmp_path):
    """A later PR adds a cell, a traffic mix, a config and a metric as new
    files plus entries; the harness finds them without other edits."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCHMARK))
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "lenet5-new"
    (tmp_path / "bench/configs/lenet5-new.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"name": "new-mix", "chunk_encoding": "ta-float32le",
         "residual_uplink": False, "rounds_per_job": 2, "tally_jobs": 2,
         "frame_loss": 0.1}))
    (tmp_path / "bench/checks/new-cell.json").write_text(
        (ROOT / "bench/checks/paper-f32.json").read_text())
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "lenet5-new", "source": cfg["source"],
                             "file": "bench/configs/lenet5-new.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "new-cell", "config": "lenet5-new",
                               "traffic": "new-mix", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "a test", "moves": "round_s",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new-cell", tmp_path)
    assert cell.config["name"] == "lenet5-new"
    assert cell.traffic["frame_loss"] == 0.1
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert "updates_per_s" not in [m["name"] for m in cell.end_to_end]
    reader = spec.load_metric_reader("new_metric", tmp_path / "bench")
    assert reader.read({}) == 42.0


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", ROOT)
    with pytest.raises(FileNotFoundError):
        spec.load_metric_reader("no_such_metric")
