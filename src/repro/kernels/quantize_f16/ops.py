"""Public ops for the f16 payload quantizer: picks Pallas (interpret on CPU,
compiled on TPU) and hands the CBOR-ready little-endian payload to the wire
path without intermediate ``bytes`` objects.

Three entry points, fastest first:

  * ``params_to_f16_view``         — a zero-copy ``memoryview`` of the
    kernel output, ready to splice into a message as a borrowed segment
    (``to_cbor_segments(..., params_payload=view)``): kernel→wire with
    **zero** host copies;
  * ``params_to_f16_payload_into`` — writes the payload into a
    caller-provided buffer (one copy, into memory the caller owns);
  * ``params_to_f16_payload``      — legacy owned ``bytes`` (one copy).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.quantize_f16.quantize_f16 import dequantize_f16, quantize_f16


def _f16_bits(flat: jax.Array) -> np.ndarray:
    """Kernel output as a host little-endian u2 array (no copy on LE hosts;
    on CPU ``np.asarray`` aliases the device buffer)."""
    bits = quantize_f16(flat, interpret=interpret_mode())
    return np.ascontiguousarray(np.asarray(bits)).astype("<u2", copy=False)


def params_to_f16_view(flat: jax.Array) -> memoryview:
    """f32 vector -> borrowed little-endian half payload view (CBOR tag 84).

    The view aliases the kernel's output buffer — splicing it into a
    vectored message costs zero copies.  It keeps that buffer alive; copy
    (``bytes(view)``) if the payload must outlive the next kernel call."""
    return memoryview(_f16_bits(flat)).cast("B").toreadonly()


def params_to_f16_payload_into(flat: jax.Array, out) -> int:
    """Quantize ``flat`` and write the payload into ``out`` (any writable
    buffer with room); returns the number of bytes written.  One copy —
    kernel output straight into the caller's wire/checkpoint buffer."""
    view = params_to_f16_view(flat)
    n = view.nbytes
    dst = out if isinstance(out, memoryview) else memoryview(out)
    if dst.ndim != 1 or dst.itemsize != 1:
        dst = dst.cast("B")
    if dst.readonly:
        raise ValueError("output buffer is read-only")
    if dst.nbytes < n:
        raise ValueError(f"output buffer too small: {dst.nbytes} < {n}")
    dst[:n] = view
    return n


def params_to_f16_array(flat) -> np.ndarray:
    """Kernel output as a host ``<f2`` array (aliases the kernel buffer on
    little-endian hosts) — the chunk-wire layout
    ``fl.chunking.chunk_stream(quantizer="kernel")`` slices into f16
    chunk payloads."""
    arr = np.ascontiguousarray(flat, dtype=np.float32).reshape(-1)
    if arr.size == 0:
        return np.empty(0, "<f2")
    return _f16_bits(arr).view("<f2")


def params_to_f16_payload(flat: jax.Array) -> bytes:
    """f32 vector -> owned little-endian half-float payload bytes."""
    return bytes(params_to_f16_view(flat))


def f16_payload_to_params(payload) -> np.ndarray:
    bits = np.frombuffer(payload, dtype="<u2")
    out = dequantize_f16(jax.numpy.asarray(bits), interpret=interpret_mode())
    return np.asarray(out)
