"""Public op: weighted FedAvg over stacked client updates."""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.fedavg.fedavg import fedavg_reduce


def fedavg_aggregate(updates: np.ndarray, dataset_sizes: np.ndarray) -> np.ndarray:
    """updates (K, n), dataset_sizes (K,) -> FedAvg'd flat params (n,)."""
    out = fedavg_reduce(jax.numpy.asarray(updates, jax.numpy.float32),
                        jax.numpy.asarray(dataset_sizes, jax.numpy.float32),
                        interpret=interpret_mode())
    return np.asarray(out)
