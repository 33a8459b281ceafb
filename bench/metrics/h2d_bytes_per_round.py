"""Host-to-device bytes (training and evaluation batches, the installed
global's leaves) per round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.counted(w, "h2d_bytes") / w["rounds"]
