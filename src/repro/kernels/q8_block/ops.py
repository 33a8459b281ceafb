"""Public ops for blockwise int8 compression of model updates.

``compress_update`` / ``decompress_update`` are the numeric API;
``compress_update_into`` writes the kernel's outputs into caller-provided
buffers (one copy, into memory the caller owns), and ``q8_wire_item``
returns the CBOR ``fl-model-params`` object tree whose arrays alias the
kernel output — the vectored encoder splices them onto the wire as
borrowed segments, so kernel→wire needs no intermediate ``bytes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.q8_block.q8_block import BLOCK, dequantize_q8, quantize_q8


def _quantize_blocks(flat: jax.Array):
    n = flat.shape[0]
    pad = (-n) % BLOCK
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
    return quantize_q8(blocks, interpret=interpret_mode())


def compress_update(flat: jax.Array):
    """f32 vector -> (int8 values, f32 scales, reconstruction error)."""
    n = flat.shape[0]
    q, scales = _quantize_blocks(flat)
    deq = dequantize_q8(q, scales, interpret=interpret_mode()).reshape(-1)[:n]
    return q.reshape(-1)[:n], scales, flat - deq


def compress_update_into(flat: jax.Array, q_out, scales_out
                         ) -> tuple[int, int]:
    """Quantize ``flat`` and write the block-padded int8 values and f32
    scales into caller buffers; returns (q_bytes, scales_bytes) written.

    One copy per output — kernel buffer straight into the caller's wire /
    checkpoint memory, no intermediate ``bytes``.  ``q_out`` receives the
    *padded* value stream (``ceil(n / BLOCK) * BLOCK`` bytes), matching
    the q8 wire payload layout."""
    q, scales = _quantize_blocks(flat)
    q_np = np.ascontiguousarray(np.asarray(q).reshape(-1))
    s_np = np.ascontiguousarray(np.asarray(scales)).astype("<f4", copy=False)
    dst_q = np.frombuffer(q_out, dtype=np.int8, count=q_np.size)
    dst_s = np.frombuffer(scales_out, dtype="<f4", count=s_np.size)
    np.copyto(dst_q, q_np)
    np.copyto(dst_s, s_np)
    return q_np.nbytes, s_np.nbytes


def q8_wire_item(flat: jax.Array):
    """The kernel's q8 output as a CBOR fl-model-params object tree
    (``params_codec.q8_item_from_arrays`` defines the layout).

    The arrays alias the kernel output buffers, so the vectored encoder
    puts them on the wire as borrowed segments — zero host copies."""
    from repro.core.params_codec import q8_item_from_arrays

    q, scales = _quantize_blocks(flat)
    return q8_item_from_arrays(np.asarray(q).reshape(-1), np.asarray(scales),
                               int(flat.shape[0]), BLOCK)


def q8_chunk_arrays(flat):
    """Kernel quantization in chunk-wire layout: f32 vector ->
    (block-padded int8 values, ``<f4`` scales, reconstruction error) as
    host arrays — what ``fl.chunking.chunk_stream(quantizer="kernel")``
    slices into scale-block-aligned ``Q8ChunkPayload``s.  The returned
    arrays alias the kernel output where the host layout allows, so the
    vectored encoder borrows the chunk slices without copying."""
    flat_np = np.ascontiguousarray(flat, dtype=np.float32).reshape(-1)
    n = flat_np.size
    if n == 0:
        return (np.empty(0, np.int8), np.empty(0, "<f4"),
                np.empty(0, np.float32))
    q, scales = _quantize_blocks(jnp.asarray(flat_np))
    deq = dequantize_q8(q, scales, interpret=interpret_mode()).reshape(-1)[:n]
    q_np = np.ascontiguousarray(np.asarray(q).reshape(-1))
    s_np = np.ascontiguousarray(np.asarray(scales)).astype("<f4", copy=False)
    return q_np, s_np, flat_np - np.asarray(deq)


def decompress_update(q: np.ndarray, scales: np.ndarray, n: int) -> np.ndarray:
    pad = (-n) % BLOCK
    qb = jnp.pad(jnp.asarray(q), (0, pad)).reshape(-1, BLOCK)
    out = dequantize_q8(qb, jnp.asarray(scales), interpret=interpret_mode())
    return np.asarray(out.reshape(-1)[:n])
