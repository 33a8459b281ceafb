"""Faults planted underneath the timed path, and the control, for showing
that the comparison in ``check`` fails what it must.

Each fault patches the program's classes for the length of a ``with``
block and is undone after it:

* ``unchanged``: a round installs nothing; the global stays as it was.
* ``half``: the fold leaves out every other update and averages the rest.
* ``altered``: the first uplink of each round is produced with its values'
  signs flipped.

The control is the reference itself computed in bfloat16, one step below
the float32 the configurations state, put in the program's place.
"""
from __future__ import annotations

import contextlib

import numpy as np

from fedbench.probes import RoundRecord

FAULTS = ("unchanged", "half", "altered")


@contextlib.contextmanager
def planted(fault: str):
    from repro.fl import aggregation, client, server

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "unchanged":
        def finalize(self):
            self._agg, self._agg_base = None, None
            return self.global_params
        patch(server.FLServer, "finalize_aggregation", finalize)
    elif fault == "half":
        add = aggregation.RunningFedAvg.add
        calls = [0]

        def half_add(self, params, dataset_size):
            calls[0] += 1
            if calls[0] % 2:
                add(self, params, dataset_size)
        patch(aggregation.RunningFedAvg, "add", half_add)
    elif fault == "altered":
        stream = client.chunk_stream
        seen = set()

        def altered_stream(model_id, round_, params, *args, **kwargs):
            if (model_id, round_) not in seen:
                seen.add((model_id, round_))
                params = -np.asarray(params)
            return stream(model_id, round_, params, *args, **kwargs)
        patch(client, "chunk_stream", altered_stream)
    else:
        raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def control_record(control_rounds, record) -> list[RoundRecord]:
    """The control's rounds (``reference.RefRound``) in the shape of the
    program's record, over the same protocol decisions."""
    out = []
    for cr, rec in zip(control_rounds, record):
        out.append(RoundRecord(
            losses=dict(cr.losses), uploaded=list(rec.uploaded),
            folded={c: (cr.updates[c], cr.weights[c]) for c in rec.folded},
            global_after=cr.global_after, installed=rec.installed,
            reporters=list(rec.reporters)))
    return out
