"""Device-to-host bytes (trained leaves pulled for the uplink, loss
scalars) per round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.counted(w, "d2h_bytes") / w["rounds"]
