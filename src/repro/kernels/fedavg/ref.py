"""Pure-jnp oracle for the weighted FedAvg reduction."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# kernel vs oracle: both sum K f32 products, in different orders
RTOL = ATOL = 2e-6


def fedavg_ref(updates: jax.Array, weights: jax.Array) -> jax.Array:
    """updates (K, n) f32, weights (K,) -> (n,) weighted average.

    ``Precision.HIGHEST``: a TPU otherwise contracts f32 in bf16 passes,
    and the oracle would be less exact than the kernel it checks."""
    w = weights / weights.sum()
    return jnp.einsum("k,kn->n", w.astype(jnp.float32),
                      updates.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
