"""Pallas TPU kernel: fused blockwise-int8 quantize with per-block scales.

Each grid step loads a (ROWS_PER_STEP, 256) tile of quantization blocks into
VMEM, computes per-row absmax (VPU cross-lane reduce), derives scales, and
writes both the int8 tile and the scale column — one HBM pass for what the
unfused reference does in three (absmax read, scale bcast read, write).
256-wide blocks = 2 x 128 lanes; int8 output tiling (32, 128) is satisfied
by ROWS_PER_STEP = 32k/256 = 128 rows.  Scales travel as a (rows, 1)
column: a 1-D scale block has no layout the v5e compiler accepts.

Both divisions (absmax / 127 and x / scale) are computed exactly, by
integer long division on the f32 bit patterns (``div_rn``): a compiler may
turn a float division into a reciprocal multiply (XLA does for division by
a constant) that is off by an ulp, and an ulp moves a scale byte or, at a
rounding tie, a q8 value.  So the kernel's output is bit-identical to
``core.params_codec.quantize_q8``.  The exception is a block whose absmax is
below 127 * 2^-126 (about 1.5e-36): its scale would be an f32 subnormal,
which the TPU's float units flush, and the kernel uses scale 1 there.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 256          # quantization block (matches core/params_codec)
ROWS_PER_STEP = 128  # rows of blocks per grid step


def div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """Correctly rounded (round-to-nearest-even) ``a / b`` for f32
    ``a >= 0`` and normal ``b > 0``, in integer arithmetic only.

    Long division of the 24-bit significands yields 24 quotient bits plus
    a guard bit, the remainder is the sticky bit.  Quotients below the
    normal range, and zero or subnormal ``a``, give 0."""
    u32 = partial(jnp.asarray, dtype=jnp.uint32)
    ab = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bb = jax.lax.bitcast_convert_type(b, jnp.uint32)
    ea = (ab >> 23).astype(jnp.int32)
    eb = (bb >> 23).astype(jnp.int32)
    ma = (ab & u32(0x7FFFFF)) | u32(0x800000)
    mb = (bb & u32(0x7FFFFF)) | u32(0x800000)
    lt = ma < mb                 # normalise: the first quotient bit is 1
    r = jnp.where(lt, ma << 1, ma)
    e = ea - eb + 127 - lt.astype(jnp.int32)
    q = jnp.zeros_like(ma)
    for _ in range(25):          # r < 2 * mb < 2^25 throughout
        ge = r >= mb
        q = (q << 1) | ge.astype(jnp.uint32)
        r = jnp.where(ge, r - mb, r) << 1
    q24, guard = q >> 1, q & u32(1)
    q24 = q24 + (guard & ((r != 0) | (q24 & u32(1))).astype(jnp.uint32))
    # a rounding carry into bit 24 bumps the exponent, as it should
    bits = ((e - 1) << 23) + q24.astype(jnp.int32)
    out = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where((e <= 0) | (ea == 0), 0.0, out)


def _q8_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                   # (R, BLOCK) f32
    ax = jnp.abs(x)
    absmax = ax.max(axis=1, keepdims=True)           # (R, 1)
    scales = div_rn(absmax, jnp.full_like(absmax, 127.0))
    scales = jnp.where(scales == 0, 1.0, scales)
    q = jnp.minimum(jnp.round(div_rn(ax, scales)), 127.0)
    q_ref[...] = jnp.where(x < 0, -q, q).astype(jnp.int8)
    s_ref[...] = scales


def _dq8_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def quantize_q8(x: jax.Array, *, interpret: bool):
    """x (nblocks, BLOCK) f32 -> (q int8 (nblocks, BLOCK), scales (nblocks,))."""
    rows = x.shape[0]
    block = min(ROWS_PER_STEP, rows)
    grid = (rows + block - 1) // block
    q, scales = pl.pallas_call(
        _q8_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block, BLOCK), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((block, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        interpret=interpret,
    )(x)
    return q, scales.reshape(rows)


@partial(jax.jit, static_argnames=("interpret",))
def dequantize_q8(q: jax.Array, scales: jax.Array, *,
                  interpret: bool) -> jax.Array:
    rows = q.shape[0]
    block = min(ROWS_PER_STEP, rows)
    grid = (rows + block - 1) // block
    return pl.pallas_call(
        _dq8_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((block, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32),
        interpret=interpret,
    )(q, scales.reshape(rows, 1))
