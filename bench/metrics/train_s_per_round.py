"""Local training (``FLClient.train_locally``: the jitted step, the eager
SGD update, the loss evaluation): host seconds per round in the window."""
from fedbench import probes


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    return ctx["spans"].total(probes.TRAIN, ctx["t0"], ctx["t1"]) / ctx["rounds"]
