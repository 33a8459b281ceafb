"""Device waits (``fl.wait``: the host blocked on device values before it
reads them, the clients' losses and trained leaves): host seconds per
round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.span_s(w, "total_s", "fl.wait") / w["rounds"]
