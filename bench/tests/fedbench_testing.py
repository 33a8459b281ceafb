"""Shared by the benchmark's tests: import paths and a tiny cell that a CPU
test run can hold."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from fedbench import spec  # noqa: E402


def tiny_cell(name: str, clients: int = 2, samples: int = 48) -> spec.Cell:
    """``name``'s cell with its own traffic, limits and metrics, cut to
    ``clients`` clients of ``samples`` rows (one SGD step each)."""
    cell = spec.load_cell(name, ROOT)
    cfg = dict(cell.config, num_clients=clients, clients_per_round=clients,
               samples_per_client={"mean": samples, "std": 0,
                                   "min": samples},
               min_fraction=0.5)
    if cfg["deadline_s"] is not None:
        cfg["deadline_s"] = {k: 1e9 for k in cfg["deadline_s"]}
    return dataclasses.replace(cell, config=cfg)
