"""Model-pytree <-> TinyFL payload codec.

The paper serializes "the model" as a flat list of floats (§V-A1).  This
module provides the flattening contract plus the encodings evaluated in the
paper (dynamic CBOR floats, f16/f32/f64 typed arrays) and two beyond-paper
compressed update paths used by the datacenter FL/distribution layer:

  * blockwise int8 quantization (per-block absmax scale) with error feedback;
  * delta encoding against a base round (send param - base, which quantizes
    much better than raw weights once training converges).

All compressed payloads remain valid TinyFL `fl-model-params` items (typed
arrays / CBOR structures validated by core/cddl.py), so a paper-faithful
decoder interoperates with the uncompressed paths.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from repro import obs
from repro.core import cbor
from repro.core.cbor import Tag
from repro.core.typed_arrays import (
    TAG_SINT8,
    decode_typed_array,
    encode_typed_array,
)

Pytree = Any

TAG_Q8_BLOCK = 0x10002  # FCFS ext: [block_size, count, ta-sint8, ta-f32 scales]

# Canonical q8 scale-block width.  ``kernels/q8_block`` compiles for the
# same BLOCK; the chunk protocol's scale-block alignment rule is stated in
# terms of this constant (docs/chunk_protocol.md).
Q8_BLOCK = 256

# Largest per-block group a wire item may claim.  The block size fans out
# into a reshape of the (untrusted) value stream, so it gets the same
# bounded-before-use treatment as chunk geometry (MAX_ASSEMBLY_ELEMS /
# MAX_NACK_CHUNKS): a forged block cannot drive a degenerate reshape or a
# scales array wildly out of proportion to the payload that arrived.
MAX_Q8_BLOCK = 1 << 16


@dataclass(frozen=True)
class ParamsSpec:
    """Structure needed to rebuild a pytree from a flat vector."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]

    @property
    def total(self) -> int:
        return sum(int(np.prod(s)) for s in self.shapes)


def flatten_params(params: Pytree) -> tuple[np.ndarray, ParamsSpec]:
    leaves, treedef = jax.tree.flatten(params)
    on_device = [l for l in leaves if isinstance(l, jax.Array)]
    if on_device:
        with obs.span(obs.WAIT):
            jax.block_until_ready(on_device)
        obs.d2h(on_device)
    arrs = [np.asarray(l) for l in leaves]
    flat = np.concatenate([a.reshape(-1).astype(np.float32) for a in arrs])
    spec = ParamsSpec(treedef, tuple(a.shape for a in arrs),
                      tuple(str(a.dtype) for a in arrs))
    return flat, spec


def unflatten_params(flat: np.ndarray, spec: ParamsSpec) -> Pytree:
    # copy=False: when the leaf dtype already matches (the chunk-assembled
    # f32 gather buffer), leaves are disjoint views of ``flat`` — installing
    # a received model costs zero extra copies.  All consumers treat params
    # functionally (optimizers return new trees), so aliasing is safe.
    out, pos = [], 0
    for shape, dtype in zip(spec.shapes, spec.dtypes):
        n = int(np.prod(shape))
        out.append(flat[pos:pos + n].reshape(shape).astype(dtype, copy=False))
        pos += n
    if pos != flat.size:
        raise ValueError(f"flat vector has {flat.size - pos} extra values")
    return jax.tree.unflatten(spec.treedef, out)


# ---------------------------------------------------------------------------
# Blockwise int8 quantization (+ error feedback)


def quantize_q8(flat: np.ndarray, block: int = Q8_BLOCK):
    """-> (int8 values, f32 per-block scales, dequantized reconstruction)."""
    n = flat.size
    pad = (-n) % block
    padded = np.pad(flat.astype(np.float32), (0, pad))
    blocks = padded.reshape(-1, block)
    scales = np.abs(blocks).max(axis=1) / 127.0
    scales = np.where(scales == 0, 1.0, scales).astype(np.float32)
    q = np.clip(np.round(blocks / scales[:, None]), -127, 127).astype(np.int8)
    deq = (q.astype(np.float32) * scales[:, None]).reshape(-1)[:n]
    return q.reshape(-1), scales, deq


def encode_q8(flat: np.ndarray, block: int = Q8_BLOCK) -> tuple[bytes, np.ndarray]:
    """CBOR item: #6.TAG_Q8_BLOCK([block, count, ta-sint8, ta-f32]).
    Returns (encoded bytes, quantization error for error feedback)."""
    q, scales, deq = quantize_q8(flat, block)
    item = (cbor.encode_tag_header(TAG_Q8_BLOCK)
            + cbor.encode_array_header(4)
            + cbor.encode(block)
            + cbor.encode(int(flat.size))
            + encode_typed_array(q)
            + encode_typed_array(scales))
    return item, flat - deq


def q8_item_from_arrays(q: np.ndarray, scales: np.ndarray, count: int,
                        block: int = Q8_BLOCK) -> Tag:
    """The single definition of the q8 wire item shape:
    ``Tag(TAG_Q8_BLOCK, [block, count, q: ndarray, scales: ndarray])``
    with ``q`` the block-padded int8 stream.  Both the numpy quantizer
    (``q8_item``) and the Pallas kernel path (``q8_block.ops.q8_wire_item``)
    build their items here so the layouts cannot diverge."""
    return Tag(TAG_Q8_BLOCK, [int(block), int(count), q, scales])


def q8_item(flat: np.ndarray, block: int = Q8_BLOCK) -> tuple[Tag, np.ndarray]:
    """The q8 payload as a CBOR object tree instead of pre-encoded bytes.

    Encodes byte-identically to ``encode_q8`` through every codec, but the
    quantized arrays stay live numpy buffers, so the vectored encoder
    splices them as borrowed segments with zero copies.  Returns
    (item, quantization error for error feedback)."""
    q, scales, deq = quantize_q8(flat, block)
    return q8_item_from_arrays(q, scales, flat.size, block), flat - deq


def validate_q8_geometry(block: int, count: int, q_elems: int,
                         scale_blocks: int) -> tuple[int, int]:
    """Bound wire-claimed q8 geometry against the *actual* typed-array
    lengths before any reshape or allocation depends on it.

    The claimed ``block``/``count`` arrive in the same untrusted bytes as
    the payload they describe, so they must be cross-checked against what
    physically arrived (the ``MAX_ASSEMBLY_ELEMS`` discipline from chunk
    reassembly): the value stream must be exactly ``scale_blocks`` whole
    blocks, and ``count`` must land inside the final block — anything else
    is a forged or corrupt item.  Returns ``(block, count)`` as ints."""
    if (not isinstance(block, int) or isinstance(block, bool)
            or not 1 <= block <= MAX_Q8_BLOCK):
        raise ValueError(
            f"q8 block size {block!r} outside 1..{MAX_Q8_BLOCK}")
    if not isinstance(count, int) or isinstance(count, bool) or count < 0:
        raise ValueError(f"q8 count {count!r} must be a uint")
    if q_elems != scale_blocks * block:
        raise ValueError(
            f"q8 value stream carries {q_elems} values, scales claim "
            f"{scale_blocks} blocks of {block}")
    if not count <= q_elems < count + block:
        raise ValueError(
            f"q8 count {count} inconsistent with {q_elems} block-padded "
            f"values (block {block})")
    return block, count


def _q8_wire_arrays(item: Tag) -> tuple[int, int, np.ndarray, np.ndarray]:
    """Decode + geometry-check a q8 wire item -> (block, count, q, scales).
    ``q`` is the block-padded int8 stream, ``scales`` the per-block f32
    scales — both zero-copy views of the item's typed-array payloads."""
    if not isinstance(item, Tag) or item.tag != TAG_Q8_BLOCK:
        raise TypeError("not a q8 payload")
    if not isinstance(item.value, (list, tuple)) or len(item.value) != 4:
        raise ValueError("q8 payload must be [block, count, values, scales]")
    block, count, q_ta, s_ta = item.value
    q = decode_typed_array(q_ta)
    scales = decode_typed_array(s_ta)
    if q.dtype != np.int8:
        raise ValueError("q8 values must be a ta-sint8 array")
    if scales.dtype != np.dtype("<f4"):
        raise ValueError("q8 scales must be a ta-float32le array")
    block, count = validate_q8_geometry(block, count, q.size, scales.size)
    return block, count, q.reshape(-1), scales.reshape(-1)


def decode_q8(item: Tag, total: int | None = None) -> np.ndarray:
    block, count, q, scales = _q8_wire_arrays(item)
    if total is not None and not 0 <= total <= count:
        raise ValueError(f"q8 requested length {total} exceeds count {count}")
    deq = (q.astype(np.float32).reshape(-1, block)
           * scales[:, None]).reshape(-1)
    return deq[:total if total is not None else count]


@dataclass(frozen=True, eq=False)
class Q8ChunkPayload:
    """One chunk's q8-block wire payload (docs/chunk_protocol.md).

    The scale-block alignment rule makes every chunk self-describing:
    chunk boundaries fall on multiples of ``block`` params, so a chunk
    carries its int8 values plus *exactly* its scale blocks — it can be
    CRC-verified, repaired, and dequantized without any other chunk.
    ``q`` is the block-padded int8 stream (padding only ever on the final
    chunk of a generation), ``count`` the unpadded element count, and the
    geometry is validated against the actual array lengths on
    construction (`validate_q8_geometry`), so a forged wire claim fails
    here instead of mis-reshaping downstream."""

    block: int
    count: int
    q: np.ndarray           # int8, block-padded values
    scales: np.ndarray      # <f4, one per block

    def __post_init__(self) -> None:
        q = np.asarray(self.q).reshape(-1)
        scales = np.ascontiguousarray(self.scales, dtype="<f4").reshape(-1)
        if q.dtype != np.int8:
            q = np.ascontiguousarray(q, dtype=np.int8)
        elif not q.flags.c_contiguous:
            q = np.ascontiguousarray(q)
        object.__setattr__(self, "q", q)
        object.__setattr__(self, "scales", scales)
        validate_q8_geometry(self.block, self.count, q.size, scales.size)

    def __eq__(self, other: object) -> bool:
        # array fields need elementwise-aware equality (the dataclass
        # default would bubble numpy's ambiguous-truth ValueError)
        if not isinstance(other, Q8ChunkPayload):
            return NotImplemented
        return (self.block == other.block and self.count == other.count
                and np.array_equal(self.q, other.q)
                and np.array_equal(self.scales, other.scales))

    __hash__ = None

    @property
    def padded(self) -> bool:
        """True when the final block is partial (only legal on the last
        chunk of a generation — the alignment rule)."""
        return self.q.size != self.count

    def item(self) -> Tag:
        """The CBOR wire object (`q8_item_from_arrays` layout); its arrays
        alias this payload, so the vectored encoder borrows them."""
        return q8_item_from_arrays(self.q, self.scales, self.count,
                                   self.block)

    def crc_segments(self) -> tuple[memoryview, memoryview]:
        """The *encoded* payload bytes the chunk CRC32 covers: the int8
        value stream, then the little-endian f32 scales (in wire order)."""
        return (memoryview(self.q).cast("B"),
                memoryview(self.scales).cast("B"))

    def dequantize_into(self, out: np.ndarray) -> None:
        """Reconstruct this chunk's ``count`` f32 params into ``out`` (a
        gather-buffer slot of exactly ``count`` elements)."""
        deq = (self.q.astype(np.float32).reshape(-1, self.block)
               * self.scales[:, None]).reshape(-1)
        out[...] = deq[:self.count]

    def to_f32(self) -> np.ndarray:
        out = np.empty(self.count, dtype="<f4")
        self.dequantize_into(out)
        return out

    def copy_owned(self) -> "Q8ChunkPayload":
        """An owned copy (wire decodes alias a receive ring's arena — a
        parked chunk must outlive it)."""
        return Q8ChunkPayload(self.block, self.count,
                              self.q.copy(), self.scales.copy())


def q8_chunk_payload(item: Tag) -> Q8ChunkPayload:
    """Decode a q8 wire item into a geometry-checked chunk payload whose
    arrays are zero-copy views of the item's typed arrays."""
    block, count, q, scales = _q8_wire_arrays(item)
    return Q8ChunkPayload(block, count, q, scales)


@dataclass
class ErrorFeedback:
    """Residual accumulator: the quantization error of round t is added back
    before quantizing round t+1 (keeps compressed FL/SGD convergent)."""

    residual: np.ndarray | None = None

    def compensate(self, flat: np.ndarray) -> np.ndarray:
        if self.residual is None:
            return flat
        return flat + self.residual

    def update(self, error: np.ndarray) -> None:
        self.residual = error


# ---------------------------------------------------------------------------
# Delta encoding


def delta_encode(flat: np.ndarray, base: np.ndarray) -> np.ndarray:
    return flat - base


def delta_decode(delta: np.ndarray, base: np.ndarray) -> np.ndarray:
    return base + delta


# ---------------------------------------------------------------------------
# Top-k sparsification (beyond-paper; CBOR map {indices: ta-u32, values: ta-f16})


def encode_topk(flat: np.ndarray, k: int) -> tuple[bytes, np.ndarray]:
    idx = np.argpartition(np.abs(flat), -k)[-k:].astype(np.uint32)
    idx.sort()
    vals = flat[idx].astype(np.float16)
    item = (cbor.encode_array_header(3)
            + cbor.encode(int(flat.size))
            + encode_typed_array(idx)
            + encode_typed_array(vals))
    dense = np.zeros_like(flat)
    dense[idx] = vals.astype(np.float32)
    return item, flat - dense


def decode_topk(item: list) -> np.ndarray:
    total, idx_ta, val_ta = item
    out = np.zeros(int(total), np.float32)
    idx = decode_typed_array(idx_ta)
    out[idx] = decode_typed_array(val_ta).astype(np.float32)
    return out
