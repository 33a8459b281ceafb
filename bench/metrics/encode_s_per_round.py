"""Uplink codec (``FLClient.local_model_chunks``: flatten, residual, error
feedback, quantize, chunk framing): host seconds per round in the window."""
from fedbench import probes


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    return ctx["spans"].total(probes.ENCODE, ctx["t0"], ctx["t1"]) / ctx["rounds"]
