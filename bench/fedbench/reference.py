"""The plain reference of one federation job, independent of the program.

Given what the benchmark made (the clients' data, the initial global) and
which clients the protocol had train, upload and fold in each round (the
seeded decisions of dropout, deadline and quorum), it computes what the
cell's configuration says the rounds produce:

* downlink: the global as the cohort installs it (q8-block round trip for
  a q8 downlink, the f32 vector otherwise);
* local training: ``local_epochs`` of SGD over shuffled batches, the last
  partial batch dropped, then the mean loss on the first 256 train and
  validation rows (the client's split and batch order follow the rules
  ``FLClient`` documents: ``default_rng((seed, client))`` permutes the
  rows, the first fifth validates; ``default_rng((seed, client, round))``
  orders each epoch);
* uplink: the update as the server receives it (the local model, or its
  difference from the installed global; for q8 with error feedback);
* fold: FedAvg weighted by training rows, in float64, then float32.

Training runs one jitted call per client (one small program for every
client: the steps a client lacks are masked), in float32 at ``highest``
matmul precision, or in bfloat16 for the control.  A batch is every key of
the configuration's data at the same rows; the control casts its floating
arrays and the frozen weights, and leaves integer arrays (ids, classes) as
they are.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

Q8_BLOCK = 256      # the q8-block wire format's scale block
EVAL_ROWS = 256     # rows each client's reported losses are taken over


def q8_roundtrip(x: np.ndarray) -> np.ndarray:
    """Blockwise int8 with per-block absmax/127 scales, round half to
    even, then back to float32."""
    x = np.asarray(x, np.float32)
    n = x.size
    blocks = np.pad(x, (0, (-n) % Q8_BLOCK)).reshape(-1, Q8_BLOCK)
    scales = np.abs(blocks).max(axis=1) / np.float32(127)
    scales[scales == 0] = 1
    q = np.clip(np.rint(blocks / scales[:, None]), -127, 127)
    return (q * scales[:, None]).reshape(-1)[:n].astype(np.float32)


def client_split(size: int, seed: int, client: int, val_fraction: float
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(train rows, validation rows), local indices."""
    perm = np.random.default_rng((seed, client)).permutation(size)
    n_val = max(1, int(size * val_fraction))
    return perm[n_val:], perm[:n_val]


@dataclass
class RefRound:
    base: np.ndarray
    global_after: np.ndarray
    losses: dict[int, tuple[float, float]] = field(default_factory=dict)
    updates: dict[int, np.ndarray] = field(default_factory=dict)
    weights: dict[int, int] = field(default_factory=dict)


class JobReference:
    """Follows one job's rounds; ``dtype`` bfloat16 makes the control.

    Built from the run's ``deploy.Deployment``, of which it keeps what the
    benchmark made (data, plans, frozen weights) and nothing of the
    program, so that the program's state can be freed before it runs."""

    def __init__(self, dep, dtype=jnp.float32) -> None:
        config = dep.config
        self.model, self.config, self.traffic = dep.model, config, dep.traffic
        self.seed, self.plans, self.dtype = dep.seed, dep.plans, dtype
        self.data, self.frozen = dep.data, dep.frozen
        self.batch = config["batch_size"]
        self.splits = [client_split(p.size, self.seed, i,
                                    config["val_fraction"])
                       for i, p in enumerate(self.plans)]
        self.steps = config["local_epochs"] * max(
            len(tr) // self.batch for tr, _ in self.splits)
        shapes = jax.eval_shape(self.model.init, jax.random.key(0))
        _, self.unravel = ravel_pytree(jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype), shapes))
        self._train = jax.jit(self._train_one)

    # -- local training ------------------------------------------------------

    def _cast(self, tree):
        """The reference's precision on floating arrays only."""
        return jax.tree.map(
            lambda a: (a.astype(self.dtype)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a), tree)

    def _train_one(self, flat0, frozen, batches, on, evals):
        """One client: its batches (each key ``(steps, batch, ...)``) with a
        mask of the steps that exist, then its two evaluation sets, each
        rows and their weights."""
        model, cast, lr = self.model, self._cast, self.config["lr"]
        kw = {} if frozen is None else {"frozen": cast(frozen)}
        params0 = cast(self.unravel(flat0))

        def step(p, xs):
            batch, keep = xs
            g = jax.grad(model.loss)(p, cast(batch), **kw)
            return jax.tree.map(lambda a, b: jnp.where(keep, a - lr * b, a),
                                p, g), None

        params, _ = jax.lax.scan(step, params0, (batches, on))
        tl, vl = (model.loss(params, cast(rows), w, **kw)
                  for rows, w in evals)
        flat = ravel_pytree(jax.tree.map(
            lambda a: a.astype(jnp.float32), params))[0]
        return flat, tl, vl

    def _rows(self, idx: np.ndarray) -> dict[str, np.ndarray]:
        return {k: v[idx] for k, v in self.data.items()}

    def _inputs(self, cid: int, round_: int):
        steps, b = self.steps, self.batch
        start = self.plans[cid].start
        train, val = self.splits[cid]
        bidx = np.zeros((steps, b), np.int64)
        on = np.zeros(steps, bool)
        rng = np.random.default_rng((self.seed, cid, round_))
        s = 0
        for _ in range(self.config["local_epochs"]):
            order = rng.permutation(len(train))
            for lo in range(0, len(train) - b + 1, b):
                bidx[s] = start + train[order[lo:lo + b]]
                on[s] = True
                s += 1
        evals = []
        for rows in (train[:EVAL_ROWS], val[:EVAL_ROWS]):
            idx = np.zeros(EVAL_ROWS, np.int64)
            idx[:len(rows)] = start + rows
            w = np.zeros(EVAL_ROWS, np.float32)
            w[:len(rows)] = 1.0
            evals.append((self._rows(idx), w))
        return self._rows(bidx), on, tuple(evals)

    def train(self, base: np.ndarray, clients: list[int], round_: int
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(local models, train losses, val losses), one row per client."""
        flats, tls, vls = [], [], []
        base = jnp.asarray(base)
        with jax.default_matmul_precision("highest"):
            for cid in clients:
                flat, tl, vl = self._train(base, self.frozen,
                                           *self._inputs(cid, round_))
                flats.append(flat)
                tls.append(tl)
                vls.append(vl)
        if not clients:
            return np.zeros((0, base.size), np.float32), np.zeros(0), \
                np.zeros(0)
        return (np.asarray(jnp.stack(flats)), np.asarray(jnp.stack(tls)),
                np.asarray(jnp.stack(vls)))

    # -- one job -------------------------------------------------------------

    def run(self, g0: np.ndarray, record) -> list[RefRound]:
        """Follow ``record``'s rounds (``probes.RoundRecord``) from ``g0``."""
        q8 = self.traffic["chunk_encoding"] == "q8-block"
        residual = self.traffic["residual_uplink"]
        g = np.asarray(g0, np.float32)
        feedback: dict[int, np.ndarray] = {}
        out = []
        for r, rec in enumerate(record):
            base = q8_roundtrip(g) if q8 else g
            trained = sorted(rec.losses)
            local, tl, vl = self.train(base, trained, r)
            rr = RefRound(base=base, global_after=g)
            for k, cid in enumerate(trained):
                rr.losses[cid] = (float(tl[k]), float(vl[k]))
            rows = {cid: local[k] for k, cid in enumerate(trained)}
            for cid in rec.uploaded:
                if cid not in rows:
                    continue        # uploaded without training: a fault
                u = rows[cid] - base if residual else rows[cid]
                if q8:
                    u = (u + feedback[cid]) if cid in feedback else u
                    u = u.astype(np.float32)
                    deq = q8_roundtrip(u)
                    feedback[cid] = u - deq
                    u = deq
                rr.updates[cid] = u
                rr.weights[cid] = len(self.splits[cid][0])
            if rec.installed:
                folded = sorted(rec.folded)
                w = np.array([rr.weights[c] for c in folded], np.float64)
                us = np.stack([rr.updates[c] for c in folded]).astype(
                    np.float64)
                avg = ((w[:, None] * us).sum(axis=0) / w.sum()).astype(
                    np.float32)
                g = ((base.astype(np.float64) + avg).astype(np.float32)
                     if residual else avg)
                rr.global_after = g
            out.append(rr)
        return out
