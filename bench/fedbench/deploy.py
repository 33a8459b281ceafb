"""Build a cell's federated deployment from its config, traffic and seed.

The traffic generator: every mix is a JSON file of parameters read here
(wire encoding, residual uplinks, frame loss, rounds per job).  Traffic is
a closed loop of federation *jobs*: job ``j`` is a fresh ``FLServer`` and
``FLSimulation`` from a global model drawn from ``(seed, j)``, run for
``rounds_per_job`` rounds on the same ``FLClient`` objects, whose volatile
state is wiped between jobs.  A fresh federation per job keeps the paper's
stop rule (a client whose validation loss falls below its training loss is
halted for good) from draining the cohort over a long window.

Sizes that vary by client (sample counts, straggler factors) are a fixed
set of quantiles of the config's distributions; the seed only permutes
which client gets which, so every seed does the same amount of work.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from types import ModuleType

import jax
import numpy as np
from jax.flatten_util import ravel_pytree


def u32(*entropy: int) -> int:
    """A 32-bit seed drawn from non-negative integers."""
    return int(np.random.SeedSequence(list(entropy)).generate_state(1)[0])


def job_seed(seed: int, job: int) -> int:
    """The selection, dropout and medium seed of job ``job`` of run
    ``seed``."""
    return u32(seed, 4, job)


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` sample counts at the mid-quantiles of N(mean, std), floored."""
    nd = statistics.NormalDist(dist["mean"], dist["std"] or 1e-12)
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.maximum(np.rint(q), dist["min"]).astype(np.int64)


def quantile_lognormal(sigma: float, n: int) -> np.ndarray:
    nd = statistics.NormalDist(0.0, 1.0)
    return np.array([math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
                     for i in range(n)])


def synthetic_mnist(n: int, rng: np.random.Generator
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Class-conditional 28x28 digits: a fixed random template per class
    plus noise, learnable within a few rounds.  Made in bulk, float32."""
    templates = rng.standard_normal((10, 28, 28, 1), dtype=np.float32)
    labels = rng.integers(0, 10, n).astype(np.int32)
    noise = rng.standard_normal((n, 28, 28, 1), dtype=np.float32)
    images = templates[labels] + np.float32(0.8) * noise
    return images, labels


@dataclass
class ClientPlan:
    """What the benchmark gave one client: its rows of the bulk data."""

    start: int
    size: int
    straggler: float


def plan_clients(config: dict, seed: int) -> list[ClientPlan]:
    n = config["num_clients"]
    rng = np.random.default_rng(u32(seed, 1))
    sizes = quantile_sizes(config["samples_per_client"], n)[rng.permutation(n)]
    strag = quantile_lognormal(config["straggler_lognormal_sigma"],
                               n)[rng.permutation(n)]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return [ClientPlan(int(a), int(s), float(f))
            for a, s, f in zip(starts, sizes, strag)]


def make_data(plans: list[ClientPlan], seed: int
              ) -> tuple[np.ndarray, np.ndarray]:
    total = sum(p.size for p in plans)
    return synthetic_mnist(total, np.random.default_rng(u32(seed, 2)))


class Deployment:
    """The program objects of one run: clients built once, a fresh server
    and simulation per job."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 model: ModuleType) -> None:
        from repro.core.params_codec import flatten_params
        from repro.fl import FLClient
        from repro.models import lenet5 as program_model
        from repro.train.optim import SGDConfig

        if config["model"] != "lenet5":
            raise ValueError(f"the FL path runs LeNet-5, not "
                             f"{config['model']!r}")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model = model
        self.plans = plan_clients(config, seed)
        self.images, self.labels = make_data(self.plans, seed)
        # the initial global of job j: one jitted call on the device
        self._key = jax.random.key(u32(seed, 3))
        self._init = jax.jit(lambda k: ravel_pytree(model.init(k))[0])
        self.spec = flatten_params(model.init(jax.random.key(0)))[1]
        if self.spec.total != config["params"]:
            raise ValueError(f"model has {self.spec.total} parameters, "
                             f"config states {config['params']}")
        sgd = SGDConfig(lr=config["lr"])
        self.clients = [
            FLClient(client_id=i,
                     data={"images": self.images[p.start:p.start + p.size],
                           "labels": self.labels[p.start:p.start + p.size]},
                     loss_fn=program_model.loss_fn, spec=self.spec,
                     local_epochs=config["local_epochs"],
                     batch_size=config["batch_size"],
                     val_fraction=config["val_fraction"], sgd=sgd,
                     seed=seed, dropout_prob=config["dropout_prob"],
                     straggler_factor=p.straggler)
            for i, p in enumerate(self.plans)]

    def initial_global(self, job: int) -> np.ndarray:
        flat = self._init(jax.random.fold_in(self._key, job))
        return np.asarray(flat, dtype=np.float32)

    def deadline_s(self) -> float | None:
        d = self.config["deadline_s"]
        if d is None:
            return None
        return float(d[self.traffic["chunk_encoding"]])

    def new_job(self, job: int, global_params: np.ndarray | None = None):
        """A fresh federation: server, simulation, wiped clients."""
        from repro.fl import FLServer, FLSimulation, OrchestrationConfig
        from repro.fl.round import RoundPolicy

        cfg, tr = self.config, self.traffic
        js = job_seed(self.seed, job)
        if global_params is None:
            global_params = self.initial_global(job)
        for c in self.clients:
            c.simulate_crash()          # wipe volatile state between jobs
        server = FLServer(OrchestrationConfig(
            num_clients=cfg["num_clients"],
            clients_per_round=cfg["clients_per_round"],
            min_fraction=cfg["min_fraction"],
            num_rounds=tr["rounds_per_job"],
            min_local_samples=cfg["min_local_samples"], seed=js),
            global_params)
        return FLSimulation(
            server, self.clients, drop_prob=tr["frame_loss"], seed=js,
            chunk_elems=cfg["chunk_elems"], uplink_mode=cfg["uplink_mode"],
            downlink_mode=cfg["downlink_mode"],
            chunk_encoding=tr["chunk_encoding"],
            residual_uplink=tr["residual_uplink"],
            round_policy=RoundPolicy(deadline_s=self.deadline_s(),
                                     train_time_s=cfg["train_time_s"]))

    def warm_clients(self, skip=()) -> None:
        """Run local training and evaluation once on each client not in
        ``skip``, so every client's jitted step and eval shapes exist
        before the window (a client that drops out of every round of the
        checked job would otherwise compile inside it)."""
        from repro.core.messages import FLGlobalModelUpdate

        g = self.initial_global(0)
        for c in self.clients:
            if c.client_id in skip:
                continue
            c.handle_global_model(FLGlobalModelUpdate(
                model_id=None, round=0, params=g, continue_training=True))
            c.train_locally()
            c.simulate_crash()
