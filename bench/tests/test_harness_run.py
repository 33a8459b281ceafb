"""``bench/run.py`` refuses to measure without a chip or without the
program, and prints no result then."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from fedbench_testing import BENCH, ROOT

ARGS = ["--workload", "paper-f32", "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert not (tmp_path / ".jax_cache").exists()
