"""§Perf H3: q8 cross-pod gradient sync — numerical validation.

The full mixed manual/auto shard_map hits an XLA SPMD-partitioner CHECK on
this XLA build (documented in EXPERIMENTS.md §Perf H3); the sync itself is
validated here on a small all-manual mesh in a subprocess with 4 host
devices: q8-compressed pod sync must equal the exact mean within blockwise
quantization error, and compress cross-pod bytes ~3.2x.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import _make_mesh
from repro.train.steps import _q8_pod_sync

mesh = _make_mesh((2, 2), ("pod", "data"))

rng = np.random.default_rng(0)
grads = {"w": jnp.asarray(rng.standard_normal((2, 512, 8)) * 0.01,
                          jnp.float32),
         "b": jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)}
# leading dim 2 = per-pod gradient replicas (sharded over "pod")

def sync(g):
    return _q8_pod_sync(g, axis="pod")

smap = jax.shard_map(sync, mesh=mesh, in_specs=(P("pod"),),
                     out_specs=P("pod"), axis_names=frozenset({"pod", "data"}),
                     check_vma=False)
synced = jax.jit(smap)(grads)

for k in grads:
    exact = np.asarray(grads[k]).mean(0)
    got = np.asarray(synced[k])[0]  # same on both pods post-sync
    got2 = np.asarray(synced[k])[1]
    np.testing.assert_allclose(got, got2, atol=1e-7)
    bound = np.abs(np.asarray(grads[k])).max() / 127.0 * 0.51 + 1e-7
    np.testing.assert_allclose(got, exact, atol=bound)
print("POD_SYNC_OK")
"""


def test_q8_pod_sync_numerics():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], cwd=REPO,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "POD_SYNC_OK" in proc.stdout


def test_q8_pod_sync_traffic_math():
    """Analytic cross-pod accounting used in EXPERIMENTS.md §Perf H3."""
    n_params = 8_537_444_352          # gemma-7b analytic param count
    pods, mb = 2, 4
    # baseline: bf16 ring all-reduce across pods, once per microbatch
    baseline = 2 * (pods - 1) / pods * n_params * 2 * mb
    # optimized: q8 all-gather (1B values + f32/256 scales), once per step
    payload = n_params * (1 + 4 / 256)
    optimized = (pods - 1) / pods * payload
    assert baseline / optimized > 12.5, baseline / optimized
