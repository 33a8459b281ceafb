"""Aggregation (``FLServer.accumulate_update``, the running FedAvg fold):
host seconds per round in the window."""
from fedbench import probes


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    return ctx["spans"].total(probes.FOLD, ctx["t0"], ctx["t1"]) / ctx["rounds"]
