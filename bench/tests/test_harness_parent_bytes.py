"""What a configuration's files make is what the harness made before they
named it, as recorded in ``data/parent_bytes.json`` from the harness that
imported the model, the data and the program by name.

The data and the initial globals are exact: their sha256 must match.  A
tiny job's reference outputs (float32 and the bfloat16 control) are float
products whose last bits follow the host (XLA's CPU backend splits a
product by its threads and vector width), so they are held to summaries:
each round's losses, and the sum, the norm and a fixed signed projection of
every update and global, within ``RTOL`` of the loss or the array's norm.
Moving the reference's threads between 1 and all cores moves them by 1e-7
of that at most; the bfloat16 control lies 1e-3 or more from float32."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from fedbench_testing import BENCH, ROOT, tiny_cell
from fedbench import spec
from fedbench.deploy import Deployment

RECORDED = json.loads(
    (BENCH / "tests" / "data" / "parent_bytes.json").read_text())
CELLS = ("paper-f32", "cohort-q8")
SEEDS = (2**31 + 11, 12345)
DTYPES = ("float32", "bfloat16")
RTOL = 1e-5


def digest(*arrays) -> str:
    """sha256 over each array's dtype, shape and bytes."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def summary(a) -> list[float]:
    """Sum, norm and a fixed signed projection of ``a``, in float64."""
    a = np.asarray(a, np.float64).ravel()
    sign = np.where(np.random.default_rng(0).random(a.size) < 0.5, -1.0, 1.0)
    return [float(a.sum()), float(np.sqrt(a @ a)), float(a @ sign)]


@pytest.fixture(scope="module")
def reference_outputs() -> dict:
    """Each tiny job's reference rounds: reported losses and summaries of
    the updates and of each round's base and global."""
    import jax.numpy as jnp

    from fedbench.harness import checked_job
    from fedbench.reference import JobReference

    out = {}
    for name in CELLS:
        for seed in SEEDS:
            dep = Deployment(tiny_cell(name), seed)
            g0, record = checked_job(dep)
            for dtype in DTYPES:
                rounds = JobReference(dep, getattr(jnp, dtype)).run(g0,
                                                                    record)
                out[f"{name}/{seed}/reference/{dtype}"] = [
                    {"losses": {str(c): [float(x) for x in v]
                                for c, v in sorted(r.losses.items())},
                     "updates": {str(c): summary(r.updates[c])
                                 for c in sorted(r.updates)},
                     "base": summary(r.base),
                     "global": summary(r.global_after)}
                    for r in rounds]
    return out


def assert_summary_close(got: list[float], want: list[float]) -> None:
    np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * want[1])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_data_and_initial_globals_hold_the_parents_bytes(name, seed):
    dep = Deployment(spec.load_cell(name, ROOT), seed)
    assert {k: digest(v) for k, v in dep.data.items()} == RECORDED[
        f"{name}/{seed}/data"]
    assert [digest(dep.initial_global(j)) for j in (0, 1)] == RECORDED[
        f"{name}/{seed}/global"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_reference_holds_the_parents_outputs(reference_outputs, name, seed,
                                             dtype):
    key = f"{name}/{seed}/reference/{dtype}"
    got, want = reference_outputs[key], RECORDED[key]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g["losses"].keys() == w["losses"].keys()
        for c in w["losses"]:
            np.testing.assert_allclose(g["losses"][c], w["losses"][c],
                                       rtol=RTOL)
        assert g["updates"].keys() == w["updates"].keys()
        for c in w["updates"]:
            assert_summary_close(g["updates"][c], w["updates"][c])
        assert_summary_close(g["base"], w["base"])
        assert_summary_close(g["global"], w["global"])
