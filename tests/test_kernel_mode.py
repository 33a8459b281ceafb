"""Where a Pallas kernel runs: compiled on tpu, interpreted on cpu, refused
anywhere else — decided per call by ``repro.kernels.interpret_mode``,
never while a module is imported."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.kernels import interpret_mode

REPO = Path(__file__).resolve().parent.parent


def test_interpret_on_cpu():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True


def test_compiled_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
def test_other_platforms_are_an_error(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=repr(platform)):
        interpret_mode()


def _fedavg(x):
    from repro.kernels.fedavg.ops import fedavg_aggregate
    return fedavg_aggregate(np.stack([x, x]), np.array([1.0, 2.0]))


def _q8(x):
    from repro.kernels.q8_block.ops import q8_chunk_arrays
    return q8_chunk_arrays(x)


def _f16(x):
    from repro.kernels.quantize_f16.ops import params_to_f16_array
    return params_to_f16_array(x)


@pytest.mark.parametrize("op", [_fedavg, _q8, _f16],
                         ids=["fedavg", "q8", "f16"])
def test_ops_choose_the_mode_per_call(monkeypatch, op):
    """The same imported op interprets on cpu and refuses an unknown
    platform on its next call: nothing was fixed at import."""
    x = np.linspace(-1, 1, 3000, dtype=np.float32)
    op(x)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        op(x)


_IMPORT_PROBE = """
import sys
from jax._src import xla_bridge
import repro.compile_cache, repro.data, repro.fl, repro.models.lenet5
import repro.kernels.fedavg.ops, repro.kernels.fedavg.ref
import repro.kernels.q8_block.ops, repro.kernels.quantize_f16.ops
import repro.train.optim
print(xla_bridge.backends_are_initialized(), "repro.launch.dryrun" in sys.modules)
"""


def test_imports_touch_no_backend():
    """Importing the FL path and the kernels initialises no JAX backend
    and never pulls in the dry-run launcher (which sets XLA_FLAGS)."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", _IMPORT_PROBE], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["False", "False"]
