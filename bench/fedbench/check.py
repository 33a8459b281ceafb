"""The numbers that decide ``correct``: the checked job as the program ran
it (``probes.Recorder``) against the plain reference (``reference``).

Norms are taken leaf by leaf and the worst leaf counts.  A leaf is judged
against the larger of its own reference norm and the median leaf's, since
some leaves move little.  Leaves whose reference first update is under a
thousandth of the median leaf's are left out (they move by rounding alone).

* ``loss_gap``: each round's mean reported training loss, relative gap.
* ``update_norm_gap``: the first installed round's update of the global,
  as the server applies it: gap between the norms, per leaf.
* ``change_norm_gap``: the global's change over the whole job: gap between
  the norms, per leaf.
* ``client_gap``: every folded client update (local training, uplink codec
  and reassembly): norm of the difference from the reference's, per leaf,
  against the reference's change.
* ``global_gap``: every installed global (the fold): norm of the
  difference from the reference's, per leaf, against the round's update.
* ``fold_mismatch``: rounds whose folded clients, weights or reporters
  differ from what the protocol recorded; exact, limit 0.
"""
from __future__ import annotations

from types import ModuleType

import jax
import numpy as np

NAMES = ("loss_gap", "update_norm_gap", "change_norm_gap", "client_gap",
         "global_gap", "fold_mismatch")
SKIP_BELOW = 1e-3       # of the median leaf's reference first update


def leaf_slices(model: ModuleType) -> list[slice]:
    """Each leaf's slice of the flat vector, in flattening order."""
    out, pos = [], 0
    for leaf in jax.tree.leaves(jax.eval_shape(model.init,
                                               jax.random.key(0))):
        n = int(np.prod(leaf.shape))
        out.append(slice(pos, pos + n))
        pos += n
    return out


class Leaves:
    def __init__(self, model: ModuleType) -> None:
        self.slices = leaf_slices(model)
        self.keep = np.ones(len(self.slices), bool)

    def norms(self, v: np.ndarray) -> np.ndarray:
        v = np.asarray(v, np.float64)
        return np.array([np.linalg.norm(v[s]) for s in self.slices])

    def set_keep(self, first_update: np.ndarray) -> None:
        n = self.norms(first_update)
        self.keep = n >= SKIP_BELOW * np.median(n)

    def _scale(self, ref_norms: np.ndarray) -> np.ndarray:
        return np.maximum(ref_norms, np.median(ref_norms[self.keep]))

    def norm_gap(self, prog: np.ndarray, ref: np.ndarray) -> float:
        p, r = self.norms(prog), self.norms(ref)
        return float((np.abs(p - r) / self._scale(r))[self.keep].max())

    def diff_gap(self, prog: np.ndarray, ref: np.ndarray,
                 scale: np.ndarray) -> float:
        d, s = self.norms(np.asarray(prog, np.float64) - ref), self.norms(scale)
        return float((d / self._scale(s))[self.keep].max())


def readings(model: ModuleType, traffic: dict, g0: np.ndarray, record,
             ref) -> dict[str, float]:
    """The numbers compared, from the program's record and the reference's
    rounds (``reference.RefRound``) of the same job."""
    residual = traffic["residual_uplink"]
    leaves = Leaves(model)
    out = {"fold_mismatch": 0}
    inst = [r for r, rec in enumerate(record) if rec.installed]
    if not inst:
        raise ValueError("no round of the checked job installed a global")
    first = inst[0]
    ref_first = ref[first].global_after - ref[first].base
    leaves.set_keep(ref_first)

    loss = []
    for rec, rr in zip(record, ref):
        cids = sorted(rec.losses)
        if cids:
            p = np.mean([rec.losses[c][0] for c in cids])
            q = np.mean([rr.losses[c][0] for c in cids])
            loss.append(abs(p - q) / abs(q))
    out["loss_gap"] = max(loss)
    out["update_norm_gap"] = leaves.norm_gap(
        record[first].global_after - ref[first].base, ref_first)
    out["change_norm_gap"] = leaves.norm_gap(
        record[-1].global_after - g0, ref[-1].global_after - g0)

    client, glob = [], []
    for rec, rr in zip(record, ref):
        folded = set(rec.folded)
        if (folded != set(rec.reporters) or not folded <= set(rr.updates)
                or any(rec.folded[c][1] != rr.weights[c] for c in folded)):
            out["fold_mismatch"] += 1
            continue
        for c in sorted(folded):
            up, ur = rec.folded[c][0], rr.updates[c]
            if not residual:
                up, ur = up - rr.base, ur - rr.base
            client.append(leaves.diff_gap(up, ur, ur))
        if rec.installed:
            glob.append(leaves.diff_gap(rec.global_after, rr.global_after,
                                        rr.global_after - rr.base))
    out["client_gap"] = max(client, default=0.0)
    out["global_gap"] = max(glob, default=0.0)
    return {k: float(out[k]) for k in NAMES}


def judge(values: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit.  A number
    with a limit of ``null`` is read and shown but not compared."""
    shown, ok = {}, True
    for name in NAMES:
        lim = limits[name]["limit"]
        v = values.get(name)
        shown[name] = {"value": v, "limit": lim}
        if lim is not None and (v is None or not np.isfinite(v) or v > lim):
            ok = False
    return ok, shown
