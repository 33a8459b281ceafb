"""Round engine (``fl.engine.round``, ``fl.engine.report``,
``fl.engine.finalize``, ``fl.server.encode``: the round's own bookkeeping,
progress reports, finalize and the global's encoding): their self time,
host seconds per round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.span_s(w, "self_s", "fl.engine.round", "fl.engine.report",
                          "fl.engine.finalize",
                          "fl.server.encode") / w["rounds"]
