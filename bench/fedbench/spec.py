"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

    bench/configs/<config>.json     a deployment (sizes, guarantees, source)
    bench/references/<model>.py     the plain model reference a config names
    bench/data/<data>.py            the data generator a config names
    bench/traffic/<traffic>.json    a traffic mix, read by ``fedbench.deploy``
    bench/metrics/<metric>.py       a per-layer metric's reader
    bench/checks/<workload>.json    the limits ``correct`` is judged by

A configuration names the model its clients train by three keys, none with
a default:

* ``"program_model"``: the dotted name of the program module whose
  ``loss_fn(params, batch) -> (loss, metrics)`` the clients train with.
* ``"data"``: ``bench/data/<data>.py``, whose ``make(n, rng)`` returns a
  dict of ``n`` row-aligned rows under each key (numpy arrays, made in bulk
  from the ``numpy.random.Generator`` it is given).  The dict is a batch of
  the program's ``loss_fn`` and of the reference's ``loss``.
* ``"model"``: ``bench/references/<model>.py``, which imports nothing of
  the program and provides ``init(key)`` (the trainable parameters: the
  initial global, the wire and the fold carry these alone),
  ``loss(params, batch, weights=None)`` (mean loss; ``weights`` masks
  padded rows) and ``train_flops_per_sample()``.  It may also define
  ``frozen(key)``: weights that are not federated, made once a run on the
  device and passed as the keyword ``frozen=`` to the program's
  ``loss_fn`` and to the reference's ``loss``.

A cell's reference, data and metric modules are read from the ``bench/``
directory it was loaded from (``Cell.bench_dir``).  A later cell, mix,
configuration, model or metric is new files plus new entries in
``BENCHMARK.json``; no file here changes.

Two limits of the program's ``FLClient`` bound which models that holds
for.  It counts a client's rows by one fixed key of the data
(``fl/client.py:97``), so a data generator has to return that key.  And it
jits ``loss_fn`` over ``(params, batch)`` alone, so the frozen weights,
bound with ``functools.partial``, are lowered as constants into every
client's compiled program and its cache key: fine for a small frozen part,
not for a base of hundreds of millions of parameters.  A model beyond
either needs ``FLClient`` to count rows by any key and to take the frozen
weights as an argument of its jitted step.
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


def load_data(data: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    mod = _load_module(bench_dir / "data" / f"{data}.py",
                       "fedbench_data_" + data.replace("-", "_"))
    if not callable(getattr(mod, "make", None)):
        raise TypeError(f"data generator {data!r} has no make(n, rng)")
    return mod


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
    bench_dir: Path             # where its reference, data and metrics are
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
        name=name, chips=int(wl["chips"]), bench_dir=bench_dir,
        config=config, traffic=traffic, limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])
