"""Pallas kernels for the update codec and the FedAvg fold.

Each kernel package has ``<name>.py`` (the ``pallas_call``), ``ops.py``
(the public host-facing API) and ``ref.py`` (the pure-jnp oracle).  The raw
kernels take ``interpret`` as a required keyword; the ops pick it per call
through ``interpret_mode``, never at import time.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """How a Pallas kernel runs on the current default backend: compiled
    on ``tpu`` (False), interpreted on ``cpu`` (True).  Any other platform
    is an error rather than a silent interpreter fallback, so a chip run
    can never pass on a kernel that did not run on the chip."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; "
        f"the default backend is {platform!r}")
