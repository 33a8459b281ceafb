"""Scheduler and medium (``fl.sched.downlink``, ``fl.sched.uplink``: the
dissemination engines, upload sessions and the shared-medium scheduler
with its NACK feedback): their self time, host seconds per round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.span_s(w, "self_s", "fl.sched.downlink",
                          "fl.sched.uplink") / w["rounds"]
