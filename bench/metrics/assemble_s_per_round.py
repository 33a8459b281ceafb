"""Chunk assembly (``fl.assemble``: decode a completed receive ring, CRC,
dequantize and gather the chunk, install a completed global): host
seconds per round."""
from fedbench import program


def read(ctx: dict) -> float | None:
    w = program.window(ctx)
    if w is None:
        return None
    return program.span_s(w, "self_s", "fl.assemble") / w["rounds"]
