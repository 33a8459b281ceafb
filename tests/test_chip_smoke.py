"""``chip_smoke.py``: it refuses to report success without a TPU, and its
phases run end to end on the CPU at a small size (the rehearsal a chip
run is preceded by)."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _assert_failed(proc: subprocess.CompletedProcess) -> None:
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_fails_without_a_tpu():
    proc = _run(SCRIPT, REPO)
    _assert_failed(proc)
    assert "no TPU" in proc.stderr


def test_fails_without_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run(alone, tmp_path)
    _assert_failed(proc)
    assert "no repro sources" in proc.stderr


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["fl_rounds", "kernel_chunks",
                                   "fedavg_kernel"])
def test_phase_passes_on_cpu_at_small_size(smoke, monkeypatch, capsys,
                                           phase):
    monkeypatch.setattr(smoke, "KERNEL_SIZES", (44_426, 5_000))
    monkeypatch.setattr(smoke, "FEDAVG_K", (8, 32))
    monkeypatch.setattr(smoke, "FEDAVG_N", 10_000)
    if phase == "fl_rounds":
        out = smoke.phase_fl_rounds(0, "cpu")
        assert out["leaf_platforms"] == ["cpu"]
    else:
        getattr(smoke, f"phase_{phase}")(0)
    lines = capsys.readouterr().out.splitlines()
    assert lines and all(f'"phase": "{phase}"' in ln for ln in lines)
