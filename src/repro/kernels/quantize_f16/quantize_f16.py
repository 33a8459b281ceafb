"""Pallas TPU kernel: blocked f32 -> f16-bits quantizer (and back).

TPU mapping: 1-D parameter stream reshaped to (rows, 1024) lane-aligned
tiles; each grid step moves one (BLOCK_ROWS, 1024) tile HBM->VMEM, converts
on the VPU, writes the u16 payload tile back.  1024 = 8 sublanes x 128 lanes
keeps both dtypes' native tiling happy (f32: (8,128), 16-bit: (16,128)).

The conversion is integer arithmetic on the bit patterns, not a float
convert: v5e's Mosaic cannot lower an f32<->f16 convert or a 16-bit float
bitcast, and integer ops also keep subnormals, which the TPU's float
units flush to zero.  The rounding is IEEE round-to-nearest-even with
gradual underflow and overflow to ±inf — bit-identical to
``np.ndarray.astype("<f2")`` for all 2^32 inputs, NaN payloads included.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024        # last-dim tile: multiple of 128 lanes
BLOCK_ROWS = 256    # rows per grid step -> 1 MiB f32 in VMEM per block


def _f32_bits_to_f16_bits(f: jax.Array) -> jax.Array:
    """u32 f32 bit patterns -> u32 holding the RNE f16 bit patterns."""
    u32 = partial(jnp.asarray, dtype=jnp.uint32)
    sign = (f >> 16) & u32(0x8000)
    fexp = f & u32(0x7F800000)
    fsig = f & u32(0x007FFFFF)
    # normal halves: rebias the exponent and round the 13 dropped bits to
    # nearest even; a carry out of the significand bumps the exponent, up
    # to 0x7c00 (inf) on overflow
    halfway_even = (fsig & u32(0x3FFF)) == u32(0x1000)
    normal = ((fexp - u32(0x38000000)) >> 13) + (
        (fsig + jnp.where(halfway_even, u32(0), u32(0x1000))) >> 13)
    # subnormal halves (2^-25 <= |x| < 2^-14): shift the full significand
    # right by 1..11 extra bits, then round on what the shift dropped too
    e = fexp >> 23
    shift = jnp.where((e >= 102) & (e <= 112), u32(113) - e, u32(1))
    sig = (fsig | u32(0x00800000)) >> shift
    sub_tie = ((sig & u32(0x3FFF)) == u32(0x1000)) & ((f & u32(0x7FF)) == 0)
    subnormal = (sig + jnp.where(sub_tie, u32(0), u32(0x1000))) >> 13
    # NaN keeps its top 10 payload bits; one that would truncate to the
    # inf pattern becomes 0x7c01, as numpy's converter does
    nan = jnp.where(fsig < u32(0x2000), u32(0x7C01), u32(0x7C00) | (fsig >> 13))
    h = jnp.where(fexp >= u32(0x47800000), u32(0x7C00), normal)
    h = jnp.where(fexp <= u32(0x38000000), subnormal, h)
    h = jnp.where(fexp < u32(0x33000000), u32(0), h)
    h = jnp.where((fexp == u32(0x7F800000)) & (fsig != 0), nan, h)
    return sign | h


def _f16_bits_to_f32_bits(h: jax.Array) -> jax.Array:
    """u32 holding f16 bit patterns -> u32 f32 bit patterns (exact)."""
    u32 = partial(jnp.asarray, dtype=jnp.uint32)
    sign = (h & u32(0x8000)) << 16
    hexp = (h >> 10) & u32(0x1F)
    mant = h & u32(0x3FF)
    normal = ((hexp + u32(112)) << 23) | (mant << 13)
    infnan = u32(0x7F800000) | (mant << 13)
    # subnormal halves are mant * 2^-24: normal f32 values, so the float
    # multiply is exact and nothing flushes
    sub = jax.lax.bitcast_convert_type(
        mant.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -24),
        jnp.uint32)
    out = jnp.where(hexp == 0, sub, jnp.where(hexp == 31, infnan, normal))
    return sign | out


def _quantize_kernel(x_ref, out_ref):
    out_ref[...] = _f32_bits_to_f16_bits(x_ref[...]).astype(jnp.uint16)


def _dequantize_kernel(bits_ref, out_ref):
    out_ref[...] = jax.lax.bitcast_convert_type(
        _f16_bits_to_f32_bits(bits_ref[...].astype(jnp.uint32)), jnp.float32)


def _blocked_call(kernel, x: jax.Array, out_dtype, *, interpret: bool):
    rows = x.shape[0]
    block = min(BLOCK_ROWS, rows)
    grid = (rows + block - 1) // block
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(x)


@partial(jax.jit, static_argnames=("interpret",))
def quantize_f16(x: jax.Array, *, interpret: bool) -> jax.Array:
    """x (n,) f32 -> (n,) u16 half bit patterns via VMEM-tiled blocks."""
    n = x.shape[0]
    pad = (-n) % LANES
    # bitcast before any other op touches the values: copies and pads of
    # u32 keep f32 subnormals that a float pass could flush
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    xp = jnp.pad(bits, (0, pad)).reshape(-1, LANES)
    out = _blocked_call(_quantize_kernel, xp, jnp.uint16, interpret=interpret)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("interpret",))
def dequantize_f16(bits: jax.Array, *, interpret: bool) -> jax.Array:
    n = bits.shape[0]
    pad = (-n) % LANES
    bp = jnp.pad(bits, (0, pad)).reshape(-1, LANES)
    out = _blocked_call(_dequantize_kernel, bp, jnp.float32,
                        interpret=interpret)
    return out.reshape(-1)[:n]
