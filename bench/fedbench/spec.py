"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

    bench/configs/<config>.json     a deployment (sizes, guarantees, source)
    bench/references/<model>.py     the plain model reference a config names
    bench/traffic/<traffic>.json    a traffic mix, read by ``fedbench.deploy``
    bench/metrics/<metric>.py       a per-layer metric's reader
    bench/checks/<workload>.json    the limits ``correct`` is judged by

A later cell, mix, configuration or metric is new files plus new entries in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(model: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _load_module(bench_dir / "references" / f"{model}.py",
                        f"fedbench_reference_{model}")


def load_metric_reader(metric: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    mod = _load_module(bench_dir / "metrics" / f"{metric}.py",
                       "fedbench_metric_" + metric.replace(".", "_"))
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"metric reader {metric!r} has no read(ctx)")
    return mod


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]      # the metric entries this cell reports
    per_layer: list[dict]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    bench_dir = root / "bench"
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{wl['traffic']}.json").read_text())
    limits = json.loads((bench_dir / "checks" / f"{name}.json").read_text())
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])
