"""The device's idle share over the traced job: 100 x (1 - busy / window),
busy being the union of the intervals in which an operation ran on it."""


def read(ctx: dict) -> float | None:
    tr = ctx["trace"]
    if not tr or not tr["devices"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
