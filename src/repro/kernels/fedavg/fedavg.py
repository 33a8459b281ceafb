"""Pallas TPU kernel: weighted FedAvg reduction over K client updates.

The aggregation hot-spot: server receives K decoded update vectors (K can be
hundreds) and reduces them to one weighted average.  Grid walks parameter
tiles; each step streams the (K, TILE) column block through VMEM once and
accumulates sum_k w_k * u_k on the VPU — a single HBM pass over the K x N
matrix (the naive tree_map average reads it twice and materializes
intermediates).  Weights are pre-normalized (length K, tiny) and enter as a
(K, 1) column, the output as a (1, n) row: the v5e compiler accepts neither
1-D blocks nor a 1-D contraction, and the multiply-and-sum over axis 0
keeps the fold in f32 on the VPU instead of bf16 passes on the MXU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 2048  # parameters per grid step (x K clients in VMEM)


def _fedavg_kernel(u_ref, w_ref, out_ref):
    u = u_ref[...]                       # (K, TILE) f32
    w = w_ref[...]                       # (K, 1) f32, pre-normalized
    out_ref[...] = jnp.sum(w * u, axis=0, keepdims=True)


@partial(jax.jit, static_argnames=("interpret",))
def fedavg_reduce(updates: jax.Array, weights: jax.Array, *,
                  interpret: bool) -> jax.Array:
    """updates (K, n) f32, weights (K,) f32 -> (n,) weighted average."""
    k, n = updates.shape
    w = (weights / weights.sum()).astype(jnp.float32)
    # no padding copy of the K x n matrix: the last tile's out-of-range
    # lanes only feed output lanes that Pallas drops on the write
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(pl.cdiv(n, TILE),),
        in_specs=[pl.BlockSpec((k, TILE), lambda i: (0, i)),
                  pl.BlockSpec((k, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, TILE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(updates.astype(jnp.float32), w.reshape(k, 1))
    return out[0]
