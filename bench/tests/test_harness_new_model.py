"""A model of another kind is new files only: a root that holds the
benchmark's files as they are, plus a program module, a reference with
frozen weights, a data generator of integer token rows, a configuration, a
traffic mix, check limits and entries in ``BENCHMARK.json``, runs a whole
cell through ``harness.run_cell``.

The model is a trainable classification head over a frozen embedding of
token ids, mean-pooled: the frozen part is made from the seed and held on
the device, and only the head is federated."""
from __future__ import annotations

import json
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench_testing import BENCH, ROOT
from fedbench import check, harness, probes, spec
from fedbench.deploy import Deployment, u32
from fedbench.harness import checked_job
from fedbench.reference import JobReference

import calibrate

SEEDS = (2**31 + 41, 7)
HEAD = 16 * 4 + 4       # the trainable head: (DIM, CLASSES) and a bias

PROGRAM = """
import jax
import jax.numpy as jnp

SEEN = []       # the frozen weights each trace of loss_fn was given


def loss_fn(params, batch, frozen=None):
    SEEN.append(frozen)
    x = frozen["embed"][batch["tokens"]].mean(axis=1)
    logits = x @ params["head"]["w"] + params["head"]["b"]
    ll = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(ll, batch["labels"][:, None], axis=1).mean()
    return loss, {"loss": loss}
"""

REFERENCE = """
import jax
import jax.numpy as jnp
import numpy as np

VOCAB, DIM, CLASSES = 64, 16, 4
SEEN = []       # (shape, dtype) of the frozen weights each trace was given


def init(key):
    return {"head": {"w": jax.random.normal(key, (DIM, CLASSES))
                     / np.sqrt(DIM),
                     "b": jnp.zeros((CLASSES,), jnp.float32)}}


def frozen(key):
    return {"embed": jax.random.normal(key, (VOCAB, DIM), jnp.float32)}


def loss(params, batch, weights=None, frozen=None):
    SEEN.append(jax.tree.map(lambda a: (a.shape, a.dtype), frozen))
    x = frozen["embed"][batch["tokens"]].mean(axis=1)
    logits = (x @ params["head"]["w"] + params["head"]["b"]).astype(
        jnp.float32)
    labels = batch["labels"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    if weights is None:
        return nll.mean()
    return (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def train_flops_per_sample():
    return 2 * 2 * DIM * CLASSES
"""

DATA = """
import numpy as np

VOCAB, SEQ, CLASSES = 64, 8, 4


def make(n, rng):
    tokens = rng.integers(0, VOCAB, (n, SEQ)).astype(np.int32)
    return {"tokens": tokens,
            "labels": (tokens[:, 0] % CLASSES).astype(np.int32)}
"""

# lower x (upper / lower)^0.6, as the cells' limits are set, from CPU
# readings at 14 seeds: sound runs' largest (lower) against the bfloat16
# control's smallest (upper), which fails every number on every seed
LIMITS = {"loss_gap": {"limit": 3.7e-6, "lower": 1.71e-7, "upper": 2.86e-5},
          "update_norm_gap": {"limit": 3.1e-4, "lower": 5e-7,
                              "upper": 0.0231},
          "change_norm_gap": {"limit": 4e-5, "lower": 5.2e-7,
                              "upper": 7.37e-4},
          "client_gap": {"limit": 5.3e-3, "lower": 6.9e-6, "upper": 0.448},
          "global_gap": {"limit": 8.5e-3, "lower": 1.42e-5, "upper": 0.596},
          "fold_mismatch": {"limit": 0}}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark's files unchanged, plus the toy model's new files."""
    root = tmp_path_factory.mktemp("toy")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "prog").mkdir()
    (root / "prog" / "toy_program.py").write_text(PROGRAM)
    (root / "bench/references/toy-head.py").write_text(REFERENCE)
    (root / "bench/data/toy-tokens.py").write_text(DATA)
    cfg = json.loads(
        (BENCH / "configs/lenet5-paper-6lowpan.json").read_text())
    cfg.update(name="toy-tokens", model="toy-head",
               program_model="toy_program", data="toy-tokens",
               reference="bench/references/toy-head.py", params=HEAD,
               num_clients=4, clients_per_round=4,
               samples_per_client={"mean": 64, "std": 0, "min": 64})
    (root / "bench/configs/toy-tokens.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/toy-f32.json").write_text(json.dumps(
        {"name": "toy-f32", "chunk_encoding": "ta-float32le",
         "residual_uplink": False, "rounds_per_job": 3, "tally_jobs": 2,
         "frame_loss": 0.0}))
    (root / "bench/checks/toy-cell.json").write_text(json.dumps(LIMITS))
    bench = spec.load_benchmark(ROOT)
    bench["configs"].append({"name": "toy-tokens", "source": cfg["source"],
                             "file": "bench/configs/toy-tokens.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy-tokens",
                               "traffic": "toy-f32", "chips": 1,
                               "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(root / "prog"))
        yield root
    sys.modules.pop("toy_program", None)


@pytest.fixture
def cell(toy_root):
    return spec.load_cell("toy-cell", toy_root)


def test_cell_is_read_from_its_own_root(cell, toy_root):
    assert cell.bench_dir == toy_root / "bench"
    assert spec.load_data(cell.config["data"], cell.bench_dir).make(
        3, np.random.default_rng(0))["tokens"].dtype == np.int32
    assert not (BENCH / "references" / "toy-head.py").exists()


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(cell, seed):
    result = harness.run_cell(cell, seed, 0.01, False,
                              probes.CompileMonitor(), time.perf_counter())
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(cell, seed):
    got = dict(calibrate.readings_for(cell, seed))
    assert check.judge(got["program"], cell.limits)[0], got["program"]
    ok, shown = check.judge(got["control"], cell.limits)
    assert not ok, shown


def test_frozen_weights_reach_both_sides_and_stay_off_the_wire(cell):
    seed = SEEDS[0]
    dep = Deployment(cell, seed)
    program = sys.modules["toy_program"]
    want = jax.jit(dep.model.frozen)(jax.random.key(u32(seed, 5)))
    np.testing.assert_array_equal(dep.frozen["embed"], want["embed"])

    del program.SEEN[:]
    g0, record = checked_job(dep)
    assert program.SEEN and all(f is dep.frozen for f in program.SEEN)

    for dtype in (jnp.float32, jnp.bfloat16):
        del dep.model.SEEN[:]
        JobReference(dep, dtype).run(g0, record)
        assert dep.model.SEEN and all(
            s == {"embed": ((64, 16), jnp.dtype(dtype))}
            for s in dep.model.SEEN)

    # the initial global, every upload and every global are the head alone
    assert dep.spec.total == HEAD == g0.size
    folded = [u for rec in record for u, _ in rec.folded.values()]
    assert folded and all(u.size == HEAD for u in folded)
    assert all(rec.global_after.size == HEAD for rec in record)
