"""The program's own round records (``repro.obs``) as the per-layer metric
readers see them: the rounds that started inside the readers' window,
summed.  A program without the recorder, or a window without a recorded
round, gives nothing, and the readers then report nothing."""
from __future__ import annotations


def window(ctx: dict) -> dict | None:
    """``repro.obs.window`` over ``[ctx["t0"], ctx["t1"])``, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    w = obs.window(ctx["t0"], ctx["t1"])
    return w if w["rounds"] else None


def span_s(w: dict, field: str, *names: str) -> float:
    """Seconds of ``field`` (``self_s`` or ``total_s``) over spans
    ``names``, summed."""
    return sum(w["spans"].get(n, {}).get(field, 0.0) for n in names)


def counted(w: dict, *names: str) -> int:
    return sum(w["counters"].get(n, 0) for n in names)
