"""Host-device transfers, both directions, one per array copied, per
round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.counted(w, "h2d_transfers",
                           "d2h_transfers") / w["rounds"]
