"""Round engine and transport (``fl/round.py``, the chunk schedulers and
assemblers, ``transport/``): the round span's self time, that is the round
minus local training, uplink encoding and folds, per round in the window."""
from fedbench import probes


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    spans, t0, t1 = ctx["spans"], ctx["t0"], ctx["t1"]
    inner = sum(spans.total(n, t0, t1)
                for n in (probes.TRAIN, probes.ENCODE, probes.FOLD))
    return (spans.total(probes.ROUND, t0, t1) - inner) / ctx["rounds"]
