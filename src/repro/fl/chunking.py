"""Symmetric selective-repeat chunk transfer (docs/chunk_protocol.md).

One protocol engine serves both directions of the FL round:

  * downlink — the server multicasts the global model as ``FLModelChunk``
    messages; each client NACKs the chunk indices it is missing after a
    window and the server re-multicasts only the union of the missing sets;
  * uplink — a client streams its local model update through the same
    ``FLModelChunk`` framing (CON unicast), and the *server* NACKs what it
    has not reassembled.

The pieces:

  * ``chunk_stream``      — slice a flat f32 parameter vector into CRC'd
    ``FLModelChunk`` messages (numpy views of the live vector; the vectored
    encoder splices each slice onto the wire as a borrowed segment — zero
    payload copies between the parameter vector and the link);
  * ``ChunkAssembler``    — per-receiver reassembly state: CRC verification,
    duplicate suppression, stale-round rejection, missing-set queries;
    verified payloads gather straight into one preallocated flat model
    buffer, so receiver peak memory is model + O(chunk), not 2× model;
  * ``run_selective_repeat`` — the windowed NACK round-trip over a
    ``LossyLink``, with exact byte accounting (``ChunkTransferReport``) so
    tests can assert retransmitted bytes stay below a full-stream re-send.

Feedback messages themselves traverse the lossy link: a lost NACK simply
means the sender learns nothing from that receiver this window and polls
again on the next one, so control-plane loss degrades latency, never
correctness.
"""
from __future__ import annotations

import heapq
import uuid
import zlib
from bisect import insort
from dataclasses import dataclass, field
from typing import Callable, Iterator, Sequence

import numpy as np

from repro import obs
from repro.core import cddl, fastpath
from repro.core.fastpath import ScatterPayload
from repro.core.messages import (
    CHUNK_ENCODINGS,
    MAX_NACK_CHUNKS,
    FLChunkAck,
    FLChunkNack,
    FLModelChunk,
    ParamsEncoding,
)
from repro.core.params_codec import (
    Q8_BLOCK,
    ErrorFeedback,
    Q8ChunkPayload,
    quantize_q8,
)
from repro.transport.coap import BlockReceiveRing, Code, TransferStats
from repro.transport.medium import MediumReport, SharedMedium
from repro.transport.network import (
    LossyLink,
    iter_downlink_frames,
    iter_tagged_frames,
)

# Window budget: the initial full-stream window plus up to this many repair
# windows before incomplete receivers are treated as dropouts for the round.
MAX_REPAIR_WINDOWS = 10

# Largest gather buffer (in f32 elements) the assembler will preallocate
# from *wire-claimed* geometry when the caller did not vouch for a model
# size (``expected_elems``).  The claimed ``num_chunks × chunk_elems``
# capacity comes from the same untrusted bytes as the payload it sizes —
# exactly the amplification ``MAX_NACK_CHUNKS`` guards in the NACK decoder
# — so a single forged 4 KB chunk must not be able to trigger a multi-TB
# ``np.empty``.  2^27 elements = a 512 MiB f32 buffer, far beyond any
# model a constrained link carries in one generation.
MAX_ASSEMBLY_ELEMS = 1 << 27


class GatherBufferPool:
    """Bounded free list of gather buffers, keyed by exact capacity.

    The uplink gather buffer has a short life: the assembler fills it, the
    incremental aggregator folds it into the running sum, and then it is
    garbage — only for an identically-shaped buffer to be allocated for
    the next client (and every client of every following round, since
    model geometry never changes mid-run).  Routing the spent buffer back
    through this pool drops steady-state allocation on the reassembly path
    to zero (pinned by a tracemalloc test).

    Safety: ``release`` must only be called once nothing reads the buffer
    anymore — the next ``acquire`` hands it out for overwriting.  Buffers
    are keyed by *exact* element capacity; a geometry change simply
    misses and allocates fresh (stale capacities age out by displacement,
    bounded by ``max_buffers``).
    """

    __slots__ = ("_free", "_count", "max_buffers", "hits", "misses",
                 "discards", "capacity_drops")

    def __init__(self, max_buffers: int = 8) -> None:
        self._free: dict[int, list[np.ndarray]] = {}
        self._count = 0
        self.max_buffers = max_buffers
        self.hits = 0
        self.misses = 0
        # discards: returned buffers the pool could NOT re-issue (failed
        # the dtype/layout check).  A workload whose buffers always fail —
        # e.g. a dtype drift upstream — used to degrade to zero reuse with
        # no signal at all; now the counter names the leak.
        self.discards = 0
        # capacity_drops: well-formed buffers dropped only because the
        # pool was full (expected displacement, split out so ``discards``
        # stays a pure health signal).
        self.capacity_drops = 0

    def acquire(self, capacity: int) -> np.ndarray | None:
        """A pooled ``<f4`` buffer of exactly ``capacity`` elements
        (contents undefined), or None on a miss."""
        lst = self._free.get(capacity)
        if lst:
            self.hits += 1
            self._count -= 1
            return lst.pop()
        self.misses += 1
        return None

    def release(self, arr: np.ndarray | None) -> None:
        """Return a spent gather buffer (or a completed-generation view of
        one — the base buffer is what gets pooled).  Arrays the pool
        cannot re-issue (wrong dtype/layout, borrowed memory) are dropped
        and counted in ``discards``."""
        if arr is None:
            return
        buf = arr.base if isinstance(arr.base, np.ndarray) else arr
        if (not isinstance(buf, np.ndarray) or buf.base is not None
                or buf.dtype != np.dtype("<f4") or buf.ndim != 1
                or not buf.flags.c_contiguous or not buf.flags.writeable):
            self.discards += 1
            return
        if self._count >= self.max_buffers:
            self.capacity_drops += 1
            return
        self._free.setdefault(buf.size, []).append(buf)
        self._count += 1


def chunk_payload_crc(params) -> int:
    """CRC32 over a chunk payload's *encoded* wire bytes.

    The one definition both ends share (sender in ``chunk_stream``,
    verifier in ``ChunkAssembler``), per encoding: f32/f16 — the
    little-endian float bytes exactly as the typed array carries them;
    q8 — the int8 value stream chained with the f32 scale bytes in wire
    order.  Covering the encoded bytes (not some decoded form) is what
    lets selective-repeat repair verify exactly what traveled."""
    if isinstance(params, Q8ChunkPayload):
        crc = 0
        for seg in params.crc_segments():
            crc = zlib.crc32(seg, crc)
        return crc
    arr = np.asarray(params)
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    return zlib.crc32(memoryview(arr).cast("B"))


def chunk_stream(model_id: uuid.UUID, round_: int, params: np.ndarray,
                 chunk_elems: int, *,
                 encoding: ParamsEncoding | str = ParamsEncoding.TA_F32,
                 allow_narrowing: bool = False,
                 error_feedback: ErrorFeedback | None = None,
                 quantizer: str = "numpy") -> Iterator[FLModelChunk]:
    """Slice ``params`` into ``chunk_elems``-element ``FLModelChunk``s in
    the requested wire ``encoding`` (``CHUNK_ENCODINGS``).

    Each chunk's ``crc32`` covers its *encoded* payload bytes
    (``chunk_payload_crc``), so receivers verify exactly what traveled,
    per chunk instead of per model.  Payloads are views of one
    whole-vector encode — peak memory is the encoded stream regardless of
    chunk count, and ``to_cbor_segments`` puts each view on the wire
    without copying it.

    * ``TA_F32`` (default): ``params`` must already be little-endian f32 —
      a sender holding f64 (or f16/bf16) params must opt into the lossy
      narrowing / silent upcast with ``allow_narrowing=True``, otherwise
      ``ValueError``.  Wire-compatible with pre-encoding receivers.
    * ``TA_F16``: the vector is quantized to f16 once; chunks are ``<f2``
      views of it.
    * ``Q8``: blockwise int8 (scale block width ``Q8_BLOCK``).
      ``chunk_elems`` must be a multiple of ``Q8_BLOCK`` — the scale-block
      alignment rule: chunk boundaries fall on block boundaries, so every
      chunk carries its int8 values plus exactly its own scales and is
      self-describing for CRC/repair/dequantize.  Padding to a whole
      block only ever lands in the final chunk.

    Lossy encodings accept any float input (the loss is the caller's
    explicit choice) and support ``error_feedback``: the previous round's
    quantization error is added back before quantizing and the new error
    is stored after.  ``quantizer="kernel"`` routes the quantization
    through the Pallas kernels (``kernels/quantize_f16`` / ``q8_block``);
    the default ``"numpy"`` host path is bit-compatible.
    """
    if chunk_elems <= 0:
        raise ValueError("chunk_elems must be positive")
    if isinstance(encoding, str):
        encoding = ParamsEncoding(encoding)
    if encoding not in CHUNK_ENCODINGS:
        raise ValueError(
            f"{encoding.value} is not a chunk encoding "
            f"(choose from {[e.value for e in CHUNK_ENCODINGS]})")
    if quantizer not in ("numpy", "kernel"):
        raise ValueError(f"unknown quantizer {quantizer!r}")

    flat = np.asarray(params).reshape(-1)
    if encoding is ParamsEncoding.TA_F32:
        if flat.dtype != np.dtype("<f4") and not allow_narrowing:
            raise ValueError(
                f"chunk_stream would silently convert {flat.dtype} params "
                f"to <f4 — lossy for f64, a silent upcast for f16/bf16. "
                f"Pass allow_narrowing=True to opt in, or pick a lossy "
                f"chunk encoding explicitly.")
        stream: np.ndarray | None = np.ascontiguousarray(flat, dtype="<f4")
        q = scales = None
    else:
        f32 = np.ascontiguousarray(flat, dtype="<f4")
        if error_feedback is not None:
            f32 = np.ascontiguousarray(error_feedback.compensate(f32),
                                       dtype="<f4")
        if encoding is ParamsEncoding.TA_F16:
            if quantizer == "kernel":
                from repro.kernels.quantize_f16.ops import params_to_f16_array
                stream = params_to_f16_array(f32)
            else:
                stream = f32.astype("<f2")
            if error_feedback is not None:
                error_feedback.update(f32 - stream.astype(np.float32))
            q = scales = None
        else:                                   # Q8
            if chunk_elems % Q8_BLOCK:
                raise ValueError(
                    f"q8 chunking requires chunk_elems to be a multiple of "
                    f"the scale-block width {Q8_BLOCK} (got {chunk_elems}) "
                    f"— the scale-block alignment rule")
            if quantizer == "kernel":
                from repro.kernels.q8_block.ops import q8_chunk_arrays
                q, scales, err = q8_chunk_arrays(f32)
            else:
                q, scales, deq = quantize_q8(f32, Q8_BLOCK)
                err = f32 - deq
            if error_feedback is not None:
                error_feedback.update(err)
            stream = None

    count = flat.size
    num = max(1, -(-count // chunk_elems))
    for i in range(num):
        start = i * chunk_elems
        if stream is not None:                  # f32 / f16: a flat slice
            part = stream[start : start + chunk_elems]
        else:                                   # q8: aligned block slices
            cnt = min(chunk_elems, count - start)
            b0 = start // Q8_BLOCK
            b1 = b0 + (chunk_elems // Q8_BLOCK if i < num - 1
                       else scales.size - b0)
            part = Q8ChunkPayload(Q8_BLOCK, cnt,
                                  q[b0 * Q8_BLOCK : b1 * Q8_BLOCK],
                                  scales[b0:b1])
        yield FLModelChunk(
            model_id=model_id, round=round_, chunk_index=i, num_chunks=num,
            crc32=chunk_payload_crc(part), params=part)


class ChunkAssembler:
    """Reassembles one generation (model_id, round, num_chunks) of chunks
    by gathering each verified payload straight into one preallocated flat
    model buffer.

    * CRC32 of every chunk is verified before it touches the buffer
      (``ValueError`` on mismatch — a corrupt chunk can never reach the
      assembled model);
    * duplicates (retransmits of an already-buffered or already-completed
      chunk) are counted and dropped;
    * a chunk from an *older* round than the assembler has seen is rejected
      as stale, while a newer round discards the stale partial state and
      resynchronizes.

    Memory: the old assembler buffered one owned copy per chunk and
    ``np.concatenate``-d them at completion — peak 2× model.  Now chunk
    geometry is inferred from the first chunk seen (every non-final chunk
    of a generation carries ``chunk_elems`` elements; the final one
    carries the remainder), a single ``num_chunks × chunk_elems`` f32
    buffer is allocated, and each chunk payload is written into its slot
    directly — the one receive-side copy the wire hop costs.  Peak
    receiver memory is one model buffer plus O(chunk) transients, in any
    arrival order.  If the *final* (short) chunk arrives before any
    geometry-bearing one, it is parked as a single owned copy and placed
    when the first full chunk fixes the slot width.  A sender whose chunk
    sizes are inconsistent with the generation geometry (or whose payload
    dtype inflates the slice) raises ``ValueError`` instead of silently
    growing the allocation.

    The gather buffer is sized from *wire-claimed* geometry, so the claim
    is bounded before any allocation: ``expected_elems`` (the model size
    the receiver already knows — its own parameter count) rejects any
    generation that could not be that model, and without it the capacity
    is capped at ``MAX_ASSEMBLY_ELEMS`` — a forged ``num_chunks`` cannot
    conjure a multi-TB ``np.empty`` out of one small chunk.
    """

    def __init__(self, *, expected_elems: int | None = None,
                 pool: GatherBufferPool | None = None) -> None:
        self._expected_elems = expected_elems
        self._pool = pool
        self._key: tuple | None = None           # (model_id, round, n)
        self._buf: np.ndarray | None = None      # gather target, <f4 flat
        self._received: set[int] = set()
        self._chunk_elems: int | None = None     # slot width (non-final)
        self._final_size: int | None = None      # final chunk's element count
        self._pending_final = None               # parked payload (owned)
        self._encoding: ParamsEncoding | None = None   # generation encoding
        self._q8_block: int | None = None        # generation q8 block width
        self._completed_key: tuple | None = None
        self.duplicates = 0
        self.stale_rejected = 0

    @property
    def in_progress(self) -> bool:
        return self._key is not None

    def _is_stale(self, round_: int) -> bool:
        latest = -1
        if self._key is not None:
            latest = max(latest, self._key[1])
        if self._completed_key is not None:
            latest = max(latest, self._completed_key[1])
        return round_ < latest

    def _reset_generation(self, key: tuple | None) -> None:
        self._key = key
        self._buf = None
        self._received = set()
        self._chunk_elems = None
        self._final_size = None
        self._pending_final = None
        self._encoding = None
        self._q8_block = None

    def _alloc(self, num_chunks: int) -> None:
        """Allocate the gather buffer once the slot width is known, and
        place a parked final chunk if one arrived first.  The claimed
        capacity is bounded *before* the allocation (see class docstring):
        memory here must scale with the model the receiver expects, never
        with what a wire message asserts."""
        elems = self._chunk_elems
        capacity = num_chunks * elems
        if self._expected_elems is not None:
            # exact-fit bound: num_chunks = ceil(expected / elems) implies
            # capacity < expected + elems for any legitimate chunking
            if capacity >= self._expected_elems + elems:
                raise ValueError(
                    f"generation capacity {capacity} elements cannot be a "
                    f"{self._expected_elems}-element model in {elems}-wide "
                    f"chunks")
        elif capacity > MAX_ASSEMBLY_ELEMS:
            raise ValueError(
                f"generation capacity {capacity} elements exceeds "
                f"MAX_ASSEMBLY_ELEMS ({MAX_ASSEMBLY_ELEMS}) and no "
                f"expected model size was given")
        buf = self._pool.acquire(capacity) if self._pool is not None else None
        self._buf = buf if buf is not None else np.empty(capacity, dtype="<f4")
        if self._pending_final is not None:
            fs = self._final_size
            if not 1 <= fs <= elems:
                raise ValueError(
                    f"final chunk carries {fs} elements, expected 1..{elems}")
            self._write((num_chunks - 1) * elems, self._pending_final)
            self._pending_final = None

    def _write(self, start: int, payload) -> None:
        """Reconstruct one verified payload into its gather slot: f32
        slices assign directly, f16 upcasts on assignment, q8 dequantizes
        into the slot — always exactly the payload's unpadded element
        count, whatever the wire form."""
        if isinstance(payload, Q8ChunkPayload):
            payload.dequantize_into(self._buf[start : start + payload.count])
        else:
            self._buf[start : start + payload.size] = payload

    @staticmethod
    def _normalize(msg: FLModelChunk):
        """The chunk payload in canonical wire form ->
        ``(encoding, payload, elems)`` where ``payload`` is a flat
        contiguous ``<f4``/``<f2`` view or a ``Q8ChunkPayload`` and
        ``elems`` the model elements it reconstructs.  Zero-copy when the
        sender's array already is wire-shaped (the fan-out hot path); a
        dtype-mismatched legacy sender (e.g. f64 arrays) costs exactly one
        conversion copy of one chunk and lands on the f32 path — CRC over
        f32 bytes, as those streams always defined it."""
        params = msg.params
        if isinstance(params, Q8ChunkPayload):
            return ParamsEncoding.Q8, params, params.count
        part = np.asarray(params)
        if part.dtype == np.dtype("<f2"):
            if not part.flags.c_contiguous:
                part = np.ascontiguousarray(part)
            return ParamsEncoding.TA_F16, part.reshape(-1), part.size
        if part.dtype != np.dtype("<f4") or not part.flags.c_contiguous:
            part = np.ascontiguousarray(part, dtype="<f4")
        return ParamsEncoding.TA_F32, part.reshape(-1), part.size

    def _check_encoding(self, idx: int, enc: ParamsEncoding,
                        payload) -> None:
        """Generation encoding uniformity: the first verified chunk fixes
        the encoding (and q8 block width); every later chunk must match —
        a mixed generation means a confused or hostile sender, and a
        gather buffer must never blend dequantization rules."""
        if self._encoding is None:
            self._encoding = enc
            if enc is ParamsEncoding.Q8:
                self._q8_block = payload.block
        elif enc is not self._encoding:
            raise ValueError(
                f"chunk {idx} encoding {enc.value} differs from the "
                f"generation's {self._encoding.value}")
        elif (enc is ParamsEncoding.Q8
                and payload.block != self._q8_block):
            raise ValueError(
                f"chunk {idx} q8 block {payload.block} differs from the "
                f"generation's {self._q8_block}")

    def add(self, msg: FLModelChunk) -> np.ndarray | None:
        """Verify one chunk and gather it into the model buffer; returns
        the assembled flat f32 vector once every chunk of the generation
        has arrived, else None."""
        n, idx = msg.num_chunks, msg.chunk_index
        if n < 1 or not 0 <= idx < n:
            raise ValueError(
                f"chunk index {idx} out of range for {n} chunks")
        if n > MAX_NACK_CHUNKS:
            # same untrusted-size guard as the NACK decoder: num-chunks
            # fans out into O(n) state (missing sets, range expansion)
            raise ValueError(
                f"num-chunks {n} exceeds MAX_NACK_CHUNKS ({MAX_NACK_CHUNKS})")
        enc, part, elems = self._normalize(msg)
        if chunk_payload_crc(part) != msg.crc32:
            raise ValueError(f"chunk {idx}/{n}: CRC mismatch")
        key = (msg.model_id, msg.round, n)
        if key == self._completed_key:
            self.duplicates += 1      # late retransmit of a finished round
            return None
        if key != self._key:
            if self._is_stale(msg.round):
                self.stale_rejected += 1
                return None
            self._reset_generation(key)
        if idx in self._received:
            self.duplicates += 1
            return None
        self._check_encoding(idx, enc, part)
        final = idx == n - 1
        if final and n > 1 and elems == 0:
            raise ValueError("empty final chunk")
        if not final:
            if elems == 0:
                raise ValueError("empty non-final chunk")
            if enc is ParamsEncoding.Q8 and (part.padded
                                             or elems % part.block):
                # the scale-block alignment rule: only the generation's
                # final chunk may end mid-block or carry padding
                raise ValueError(
                    f"non-final q8 chunk {idx} is not whole unpadded "
                    f"scale blocks ({elems} elements, block {part.block})")
            if self._chunk_elems is None:
                self._chunk_elems = elems
                try:
                    self._alloc(n)
                except (ValueError, MemoryError):
                    # hostile capacity, a parked final chunk inconsistent
                    # with this width, or a failed allocation: the
                    # generation is garbage — drop it whole so a clean
                    # retransmit can restart assembly from scratch
                    self._reset_generation(None)
                    raise
            elif elems != self._chunk_elems:
                raise ValueError(
                    f"chunk {idx} carries {elems} elements, generation "
                    f"width is {self._chunk_elems}")
            self._write(idx * self._chunk_elems, part)
        elif n == 1:
            # degenerate single-chunk generation: the payload is the model
            self._final_size = elems
            if enc is ParamsEncoding.Q8:
                self._buf = part.to_f32()
            elif enc is ParamsEncoding.TA_F16:
                self._buf = part.astype("<f4")
            else:
                self._buf = (part
                             if not np.may_share_memory(part, msg.params)
                             else part.copy())
        elif self._chunk_elems is None:
            # final chunk before geometry is known: park one owned copy
            # (wire decodes alias a receive ring's arena that is freed as
            # soon as the message is consumed)
            if enc is ParamsEncoding.Q8:
                self._pending_final = part.copy_owned()
            else:
                self._pending_final = (
                    part if not np.may_share_memory(part, msg.params)
                    else part.copy())
            self._final_size = elems
        else:
            if not 1 <= elems <= self._chunk_elems:
                raise ValueError(
                    f"final chunk carries {elems} elements, expected "
                    f"1..{self._chunk_elems}")
            self._final_size = elems
            self._write(idx * self._chunk_elems, part)
        self._received.add(idx)
        if len(self._received) < n:
            return None
        total = (self._final_size if n == 1
                 else (n - 1) * self._chunk_elems + self._final_size)
        flat = self._buf[:total]
        self._completed_key = key
        self._reset_generation(None)
        return flat

    def is_complete(self, model_id: uuid.UUID, round_: int) -> bool:
        ck = self._completed_key
        return ck is not None and ck[0] == model_id and ck[1] == round_

    def export_state(self) -> dict | None:
        """Snapshot the in-progress generation for a durable client
        checkpoint (crash-resume): generation key + geometry, the received
        bitmap, and the gather buffer itself.  Returns None when there is
        nothing durable to keep — no generation open, or only a parked
        final chunk (no geometry yet, so a resumed client simply NACKs the
        full stream; persisting one short chunk buys nothing)."""
        if (self._key is None or self._buf is None
                or self._chunk_elems is None):
            return None
        mid, rnd, n = self._key
        return {
            "model_id": str(mid),
            "round": int(rnd),
            "num_chunks": int(n),
            "chunk_elems": int(self._chunk_elems),
            "final_size": (-1 if self._final_size is None
                           else int(self._final_size)),
            "encoding": ("" if self._encoding is None
                         else self._encoding.value),
            "q8_block": int(self._q8_block or 0),
            "received": np.fromiter(sorted(self._received), dtype="<i4",  # sched-ok: checkpoint export, not the frame loop
                                    count=len(self._received)),
            "buf": self._buf,
        }

    def restore_state(self, st: dict) -> None:
        """Reinstall an ``export_state`` snapshot after a crash.  The
        restored assembler answers ``missing``/``feedback`` exactly as the
        pre-crash one did, so the sender's repair window retransmits only
        the chunks the checkpoint does not hold."""
        key = (uuid.UUID(str(st["model_id"])), int(st["round"]),
               int(st["num_chunks"]))
        self._reset_generation(key)
        self._chunk_elems = int(st["chunk_elems"])
        fs = int(st["final_size"])
        self._final_size = None if fs < 0 else fs
        enc = str(st["encoding"])
        self._encoding = ParamsEncoding(enc) if enc else None
        qb = int(st["q8_block"])
        self._q8_block = qb or None
        self._received = {int(i)
                          for i in np.asarray(st["received"]).reshape(-1)}
        buf = np.ascontiguousarray(np.asarray(st["buf"]).reshape(-1),
                                   dtype="<f4")
        if not buf.flags.writeable:
            buf = buf.copy()    # checkpoint restores may hand back views
        self._buf = buf

    def missing(self, model_id: uuid.UUID, round_: int,
                num_chunks: int) -> list[int]:
        """Chunk indices of the given generation not yet assembled."""
        key = (model_id, round_, num_chunks)
        if key == self._completed_key:
            return []
        if key != self._key:    # nothing buffered for this generation yet
            return list(range(num_chunks))
        return [i for i in range(num_chunks) if i not in self._received]

    def feedback(self, model_id: uuid.UUID, round_: int,
                 num_chunks: int) -> FLChunkAck | FLChunkNack:
        """The selective-repeat control message for the given generation."""
        miss = self.missing(model_id, round_, num_chunks)
        if not miss:
            return FLChunkAck(model_id, round_, num_chunks)
        return FLChunkNack(model_id, round_, num_chunks, tuple(miss))


@dataclass
class ChunkTransferReport:
    """Exact accounting for one selective-repeat transfer."""

    num_chunks: int = 0
    windows: int = 0                      # transfer windows incl. the first
    chunk_sends: int = 0                  # chunk messages sent incl. repairs
    initial_payload_bytes: int = 0        # one full stream
    payload_bytes: int = 0                # all chunk payload bytes sent
    control_messages: int = 0
    control_payload_bytes: int = 0
    lost_feedback: int = 0                # NACK/ACKs the link failed to carry
    corrupt_chunks: int = 0               # damaged in flight, re-requested
    completed: list[int] = field(default_factory=list)  # receiver positions
    stats: TransferStats = field(default_factory=TransferStats)

    @property
    def retransmitted_chunks(self) -> int:
        return self.chunk_sends - self.num_chunks

    @property
    def retransmitted_payload_bytes(self) -> int:
        return self.payload_bytes - self.initial_payload_bytes


def _validate(payload, mtype: str) -> None:
    # fastpath.decode consumes ScatterPayloads / segment lists directly,
    # so validating a vectored wire form never joins it.
    cddl.validate(fastpath.decode(payload), cddl.SCHEMAS[mtype])


def run_selective_repeat(
    link: LossyLink,
    chunks: Sequence[FLModelChunk],
    receivers: Sequence,
    *,
    uri: str,
    feedback_uri: str,
    code: Code = Code.POST,
    multicast: bool = False,
    max_windows: int = 1 + MAX_REPAIR_WINDOWS,
    validate: bool = True,
    record: Callable[[str, TransferStats], None] | None = None,
    backoff=None,
    turnaround_s: float = 0.05,
    airtime_budget_s: float | None = None,
    sender_crash: tuple[int, int] | None = None,
    feedback_lost: Callable[[int, int], bool] | None = None,
    client_ids: Sequence[int] | None = None,
    poll_first: bool = False,
) -> ChunkTransferReport:
    """Drive one selective-repeat transfer of ``chunks`` to ``receivers``.

    Each receiver is any object with

        receive_chunk(msg: FLModelChunk)                  -> buffer/install
        chunk_feedback(model_id, round, num_chunks)       -> Nack | Ack

    (``FLClient`` on the downlink; an assembler-backed server endpoint on
    the uplink; bare ``AssemblerReceiver``s in the loss-sweep harness.)

    Window 0 sends every chunk; window k>0 re-sends only the union of the
    missing sets NACK'd by receivers whose feedback survived the link.  The
    loop ends when every receiver's ACK has reached the sender or the
    window budget is spent.  ``record`` receives per-message-type
    ``TransferStats`` (``FL_Model_Chunk`` / ``FL_Chunk_Nack`` /
    ``FL_Chunk_Ack``) for round accounting.

    Round-lifecycle hooks (fl.round):

    * ``backoff`` — a ``BackoffPolicy``: repair window k waits
      ``backoff.delay(k)`` of link time first (exponential, scaled by the
      link's loss estimate) and its ``retry_budget`` replaces
      ``max_windows``;
    * ``airtime_budget_s`` — stop opening windows once the transfer has
      consumed this much round-clock time (the round's deadline share);
    * ``sender_crash`` — ``(window, n_sends)``: the sender dies in that
      window after that many chunk transmissions (FaultPlan client crash);
    * ``feedback_lost(receiver_idx, window)`` — force-lose that feedback
      message after it was accounted (FaultPlan feedback loss);
    * ``client_ids[r]`` — the FL client id behind receiver slot ``r``, so
      the link's ``chunk_drop`` schedule (a ``FaultPlan``'s chunk loss) is
      keyed by client identity, not slot position.  Without it the uplink's
      single slot would alias every client onto id 0 and a downlink
      cohort's ids would shift with selection order;
    * ``poll_first`` — crash-resume: window 0 sends *nothing* and only
      collects feedback, so a sender resuming against a receiver that
      already holds part of the stream retransmits exactly the NACK'd
      chunks.  ``initial_payload_bytes`` still prices the full stream —
      ``retransmitted_payload_bytes`` goes negative by exactly the bytes
      the resume saved, which is what the strictly-fewer-bytes tests pin.
    """
    if not chunks:
        raise ValueError("empty chunk stream")
    mid, rnd, n = chunks[0].model_id, chunks[0].round, chunks[0].num_chunks
    # Scatter-gather wire forms: each chunk is small owned header segments
    # plus a *borrowed* view of the live parameter slice.  Peak memory for
    # the whole transfer — repair windows included — is the model plus
    # O(headers), not the model plus a full encoded copy.
    wires = [ScatterPayload(c.to_cbor_segments()) for c in chunks]
    if validate:
        for w in wires:
            # segment-aware decode: the validator walks the scatter
            # segments in place — no transient per-chunk join.
            _validate(w, "FL_Model_Chunk")
    report = ChunkTransferReport(
        num_chunks=n, initial_payload_bytes=sum(len(w) for w in wires))

    complete: set[int] = set()   # receivers that assembled (ground truth)
    acked: set[int] = set()      # receivers whose ACK reached the sender
    to_send = [] if poll_first else list(range(n))
    window = 0
    if backoff is not None:
        max_windows = backoff.max_windows
    t_start = link.round_clock_s
    while window < max_windows and len(acked) < len(receivers):
        if (airtime_budget_s is not None
                and link.round_clock_s - t_start >= airtime_budget_s):
            break                # round deadline: no airtime left to repair
        if window > 0 and backoff is not None:
            # exponential medium-aware backoff before each repair window:
            # a lossy channel waits longer instead of burning its retry
            # budget back-to-back into the same conditions
            link.advance(backoff.delay(window, turnaround_s=turnaround_s,
                                       loss_estimate=link.loss_estimate()))
        crash_now = sender_crash is not None and window >= sender_crash[0]
        send_list = to_send[:sender_crash[1]] if crash_now else to_send
        if send_list:
            delivery = link.request_stream(
                [wires[i] for i in send_list], uri=uri, code=code,
                indices=send_list, num_receivers=len(receivers),
                multicast=multicast, window=window, client_ids=client_ids)
            if record:
                record("FL_Model_Chunk", delivery.stats)
            report.stats.add(delivery.stats)
            report.chunk_sends += len(send_list)
            report.payload_bytes += delivery.stats.payload_bytes
            for i in sorted(set().union(*delivery.delivered)):  # sched-ok: per-window delivery fan-out, not per-frame
                # fan out the sender-side message object: the wire bytes
                # were already validated against it, and the assembler
                # CRC-checks every chunk, so no per-delivery decode copy.
                msg = chunks[i]
                for ridx, rcv in enumerate(receivers):
                    if i in delivery.delivered[ridx]:
                        with obs.span(obs.ASSEMBLE):
                            rcv.receive_chunk(msg)
        if crash_now:
            break                # the sender died mid-window: no feedback
        # NACK round-trip: every not-yet-acked receiver reports its state.
        missing_union: set[int] = set()
        for ridx, rcv in enumerate(receivers):
            if ridx in acked:
                continue
            fb = rcv.chunk_feedback(mid, rnd, n)
            is_ack = isinstance(fb, FLChunkAck)
            if is_ack:
                complete.add(ridx)
            payload = fb.to_cbor()
            mtype = "FL_Chunk_Ack" if is_ack else "FL_Chunk_Nack"
            if validate:
                _validate(payload, mtype)
            stats = link.send_payload(payload, uri=feedback_uri,
                                      code=Code.CONTENT)
            if record:
                record(mtype, stats)
            report.stats.add(stats)
            report.control_messages += 1
            report.control_payload_bytes += len(payload)
            if stats.failed_messages or (
                    feedback_lost is not None
                    and feedback_lost(ridx, window)):
                report.lost_feedback += 1
                continue          # the sender never saw this feedback
            if is_ack:
                acked.add(ridx)
            else:
                back = FLChunkNack.from_cbor(payload, expect_num_chunks=n)
                missing_union |= set(back.missing)
        to_send = sorted(missing_union)  # sched-ok: once per repair window, not per frame
        window += 1
        report.windows = window
    report.completed = sorted(complete)  # sched-ok: end-of-transfer report
    return report


def run_medium_downlink(
    medium: SharedMedium,
    chunks: Sequence[FLModelChunk],
    receivers: Sequence,
    *,
    uri: str,
    feedback_uri: str,
    code: Code = Code.POST,
    max_windows: int = 1 + MAX_REPAIR_WINDOWS,
    validate: bool = True,
    record: Callable[[str, TransferStats], None] | None = None,
    backoff=None,
    client_ids: Sequence[int] | None = None,
    faults=None,
    checkpoint: Callable[[int], None] | None = None,
    on_crash: Callable[[int], None] | None = None,
    resume_client: Callable[[int], bool] | None = None,
) -> ChunkTransferReport:
    """Multicast dissemination of ``chunks`` over one ``SharedMedium`` —
    the downlink half of the whole-round fault domain.

    ``run_selective_repeat`` models the downlink on a per-chunk lossy
    link; this is the same window/NACK protocol at *frame* granularity on
    the shared medium: every frame is transmitted once (one airtime
    charge, ``transmit_downlink``), each listening client gets its own
    delivery verdict, and each client reassembles through per-chunk
    reorder-aware rings that persist across repair windows — so the
    downlink shares the medium's clock, RNG, blackouts, and frame faults
    with the uplink that follows it.

    Client crash-resume hooks (the client-side mirror of the server's
    ``save_agg_snapshot`` recovery):

    * ``checkpoint(client_id)`` fires after every *newly verified* chunk a
      client gathers — persist-per-chunk, the way flash-backed firmware
      downloads journal progress — so a crash loses at most in-flight
      frames, never verified chunks;
    * a ``FaultPlan`` download-phase ``ClientCrash`` kills the client
      after ``at_chunk`` verified chunks of window ``at_window``
      (``on_crash(client_id)`` wipes its volatile state);
    * a crash with ``resume=True`` restarts the client at the next window
      boundary via ``resume_client(client_id)`` — restore returns True
      when a durable checkpoint existed, and the client's next NACK then
      requests exactly the chunks the checkpoint does not hold.  A False
      restore (no checkpoint dir) degrades to a dropout for the round.

    ``report.completed`` lists the receiver *slots* that finished
    reassembly; the caller maps slots back to client ids.
    """
    if not chunks:
        raise ValueError("empty chunk stream")
    mid, rnd, n = chunks[0].model_id, chunks[0].round, chunks[0].num_chunks
    wires = [ScatterPayload(c.to_cbor_segments()) for c in chunks]
    if validate:
        for w in wires:
            _validate(w, "FL_Model_Chunk")
    report = ChunkTransferReport(
        num_chunks=n, initial_payload_bytes=sum(len(w) for w in wires))
    n_r = len(receivers)
    if client_ids is None:
        client_ids = list(range(n_r))
    busy0 = medium.busy_s

    rings: list[dict[int, BlockReceiveRing]] = [{} for _ in range(n_r)]
    delivered: list[set[int]] = [set() for _ in range(n_r)]
    crashed = [False] * n_r
    resumed = [False] * n_r
    acked: set[int] = set()      # slots whose ACK reached the server
    complete: set[int] = set()   # slots that assembled (ground truth)
    crashes: dict[int, object] = {}
    if faults is not None:
        for ridx, cid in enumerate(client_ids):
            cr = faults.client_crash(cid)
            if cr is not None and cr.phase == "download":
                crashes[ridx] = cr

    def _crash(ridx: int) -> None:
        crashed[ridx] = True
        rings[ridx].clear()      # volatile reassembly state dies with it
        delivered[ridx] = set()
        complete.discard(ridx)
        acked.discard(ridx)
        if on_crash is not None:
            on_crash(client_ids[ridx])

    def _pending() -> bool:
        # anything left to serve: a live slot not yet acked (crashed slots
        # without a successful resume are dropouts, not blockers)
        return any(not crashed[r] and r not in acked for r in range(n_r))

    to_send = list(range(n))
    window = 0
    if backoff is not None:
        max_windows = backoff.max_windows
    while window < max_windows and _pending():
        if window > 0 and backoff is not None:
            medium.advance_to(medium.clock + backoff.delay(
                window, turnaround_s=medium.turnaround_s,
                loss_estimate=medium.loss_estimate()))
        # a crash whose coordinate window never delivered enough chunks
        # (loss starved it) fires at the next window start instead —
        # mirrors UplinkSession.crash_due
        for ridx, cr in crashes.items():
            if not crashed[ridx] and not resumed[ridx] \
                    and window > cr.at_window:
                _crash(ridx)
        window_recv = [0] * n_r      # verified chunks this window (crash coord)
        wstats = TransferStats(
            messages=len(to_send),
            payload_bytes=sum(len(wires[i]) for i in to_send))
        report.chunk_sends += len(to_send)
        report.payload_bytes += wstats.payload_bytes
        for i in to_send:
            # listeners: live slots still missing this chunk, fixed for
            # the chunk's whole frame sequence (deterministic RNG order)
            slots = [r for r in range(n_r)
                     if not crashed[r] and r not in acked
                     and i not in delivered[r]]
            if not slots:
                continue
            drops = None
            if medium.chunk_drop is not None:
                drops = {client_ids[r]: bool(medium.chunk_drop(
                    uri, window, i, client_ids[r])) for r in slots}
            for frame in iter_downlink_frames(
                    [wires[i]], uri=uri, window=window, indices=[i],
                    code=code):
                out = medium.transmit_downlink(
                    frame, wstats, receivers=[client_ids[r] for r in slots],
                    drops=drops)
                for r in slots:
                    if crashed[r]:
                        continue     # died earlier in this frame loop
                    fr = out.get(client_ids[r])
                    if fr is None:
                        continue
                    ring = rings[r].get(i)
                    if ring is None:
                        ring = rings[r][i] = BlockReceiveRing()
                    ring.feed(fr.msg)
                    if not ring.complete:
                        continue
                    del rings[r][i]
                    with obs.span(obs.ASSEMBLE):
                        ok, done = _assemble(ring, receivers[r])
                    if not ok:
                        report.corrupt_chunks += 1
                        continue
                    delivered[r].add(i)
                    window_recv[r] += 1
                    if done:
                        complete.add(r)
                    if checkpoint is not None:
                        checkpoint(client_ids[r])   # persist-per-chunk
                    cr = crashes.get(r)
                    if (cr is not None and window == cr.at_window
                            and window_recv[r] >= max(1, cr.at_chunk)):
                        _crash(r)
        if record is not None and (wstats.frames or wstats.messages):
            record("FL_Model_Chunk", wstats)
        medium.stats.messages += wstats.messages
        medium.stats.payload_bytes += wstats.payload_bytes
        report.stats.add(wstats)
        # window boundary: resume crashed clients *before* the feedback
        # round-trip, so a restored client's NACK reflects its checkpoint
        for ridx, cr in crashes.items():
            if (crashed[ridx] and not resumed[ridx]
                    and getattr(cr, "resume", False)
                    and resume_client is not None):
                if resume_client(client_ids[ridx]):
                    crashed[ridx] = False
                resumed[ridx] = True    # one attempt; no checkpoint = dropout
        medium.advance_to(medium.clock + medium.turnaround_s)
        missing_union: set[int] = set()
        for r in range(n_r):
            if r in acked or crashed[r]:
                continue
            fb = receivers[r].chunk_feedback(mid, rnd, n)
            is_ack = isinstance(fb, FLChunkAck)
            if is_ack:
                complete.add(r)
            payload = fb.to_cbor()
            mtype = "FL_Chunk_Ack" if is_ack else "FL_Chunk_Nack"
            if validate:
                _validate(payload, mtype)
            ok, fstats = medium.transmit_payload(
                payload, uri=feedback_uri, code=Code.CONTENT,
                tx_client=client_ids[r])   # the client sends its NACK
            if record is not None:
                record(mtype, fstats)
            report.stats.add(fstats)
            report.control_messages += 1
            report.control_payload_bytes += len(payload)
            if not ok or (faults is not None
                          and faults.feedback_lost(client_ids[r], window)):
                report.lost_feedback += 1
                continue         # the server never saw this feedback
            if is_ack:
                acked.add(r)
            else:
                back = FLChunkNack.from_cbor(payload, expect_num_chunks=n)
                # a resumed client's held set is whatever it did NOT nack
                delivered[r] = set(range(n)) - set(back.missing)
                missing_union |= set(back.missing)
        to_send = sorted(missing_union)  # sched-ok: once per repair window, not per frame
        window += 1
        report.windows = window
    # dissemination's share of the round clock, read back by MediumReport
    medium.downlink_airtime_s = medium.clock
    medium.downlink_busy_s = medium.busy_s - busy0
    report.completed = sorted(complete)  # sched-ok: end-of-transfer report
    return report


class UplinkSession:
    """One client's selective-repeat uplink as an explicit state machine.

    ``run_selective_repeat`` drives one transfer to completion inline;
    this is the same window/NACK logic unrolled so a scheduler can step
    *many* transfers frame-by-frame over one ``SharedMedium``
    (``run_interleaved_uplinks``).  Differences from the inline engine,
    both inherent to a real shared medium:

    * loss is per *frame* (NON — no link-layer retry), so a chunk can
      arrive with holes; its reorder-aware ``BlockReceiveRing`` persists
      across repair windows, and the NACK-triggered re-send fills exactly
      the missing block NUMs (already-held blocks count as duplicates and
      are dropped) — a chunk completes once the union of its transmissions
      covers every block;
    * delivered chunks are decoded *from their rings*
      (``from_cbor_segments`` over the arena — borrowed views, no join)
      instead of fanning out sender-side objects: the receive path is the
      production shape, wire bytes in, model slots out.

    Frames are generated lazily (one in existence at a time), so a window
    over a multi-MB model still costs O(block) transient sender memory.
    """

    def __init__(self, client_id: int, chunks: Sequence[FLModelChunk],
                 receiver, *, uri: str = "fl/model/upload",
                 feedback_uri: str = "fl/model/upload/fb",
                 code: Code = Code.POST,
                 max_windows: int = 1 + MAX_REPAIR_WINDOWS,
                 validate: bool = True,
                 start_at: float = 0.0,
                 crash_at: tuple[int, int] | None = None,
                 poll_first: bool = False) -> None:
        if not chunks:
            raise ValueError("empty chunk stream")
        self.client_id = client_id
        self.chunks = list(chunks)
        self.receiver = receiver
        self.uri = uri
        self.feedback_uri = feedback_uri
        self.code = code
        self.max_windows = max_windows
        self.validate = validate
        first = self.chunks[0]
        self.model_id = first.model_id
        self.round = first.round
        self.num_chunks = first.num_chunks
        self.wires = [ScatterPayload(c.to_cbor_segments())
                      for c in self.chunks]
        if validate:
            for w in self.wires:
                _validate(w, "FL_Model_Chunk")
        self.report = ChunkTransferReport(
            num_chunks=self.num_chunks,
            initial_payload_bytes=sum(len(w) for w in self.wires))
        self.window = 0
        # poll_first (crash-resume): window 0 sends nothing, only polls —
        # the receiver's NACK scopes retransmission to what it is missing
        self.to_send: list[int] = ([] if poll_first
                                   else list(range(self.num_chunks)))
        self.acked = False          # the sender saw the receiver's ACK
        self.assembled = False      # the receiver completed reassembly
        self.rings: dict[int, BlockReceiveRing] = {}   # in-flight chunks
        self.delivered_chunks: set[int] = set()
        self.start_at = start_at    # readiness on the round clock (training)
        self.ready_at = 0.0         # turnaround gate for the next window
        self.done_at: float | None = None
        self.crash_at = crash_at    # (window, frames): client dies there
        self.crashed = False
        self.expired = False        # still unfinished at the round deadline
        self._frames = iter(())     # lazy frame source, current window
        self._lookahead = None
        self._frames_in_window = 0
        self._window_stats = TransferStats()
        self._forced: dict[int, bool] = {}   # chunk_drop verdicts, 1 window
        # staged payload bytes this window — what state-aware arbitration
        # policies (shortest-remaining-first, deadline-aware) rank by
        self.remaining_hint = 0

    @property
    def finished(self) -> bool:
        return (self.acked or self.crashed or self.expired
                or self.window >= self.max_windows)

    def crash_due(self) -> bool:
        """Has this session reached its injected crash point?  (checked
        before every transmission and window boundary)."""
        if self.crash_at is None or self.crashed:
            return False
        cw, cf = self.crash_at
        return self.window > cw or (self.window == cw
                                    and self._frames_in_window >= cf)

    def halt(self, *, expired: bool = False) -> None:
        """Stop transmitting immediately (crash or deadline expiry)."""
        if expired:
            self.expired = True
        else:
            self.crashed = True
        self._frames = iter(())
        self._lookahead = None

    @property
    def has_frame(self) -> bool:
        return self._lookahead is not None

    def _advance(self) -> None:
        self._lookahead = next(self._frames, None)


def _enqueue_window(medium: SharedMedium, s: UplinkSession) -> None:
    """Stage the session's current window: chunk_drop verdicts, payload
    accounting, and the lazy tagged-frame source."""
    s._window_stats = TransferStats(
        messages=len(s.to_send),
        payload_bytes=sum(len(s.wires[i]) for i in s.to_send))
    s._forced = {}
    if s.to_send and medium.chunk_drop is not None:
        s._forced = {i: bool(medium.chunk_drop(s.uri, s.window, i,
                                               s.client_id))
                     for i in s.to_send}
    s.report.chunk_sends += len(s.to_send)
    s.report.payload_bytes += s._window_stats.payload_bytes
    s.remaining_hint = s._window_stats.payload_bytes
    s._frames_in_window = 0
    s._frames = iter_tagged_frames(
        [s.wires[i] for i in s.to_send], uri=s.uri, client=s.client_id,
        window=s.window, indices=s.to_send, code=s.code)
    s._advance()


# What an in-flight-damaged frame can raise while its chunk is decoded or
# CRC-verified: CBORDecodeError is a ValueError subclass; misaligned
# payload bytes surface as type/shape/bounds errors from the decode layer.
# A failure here is *data* corruption, never a programming error escape
# hatch: the chunk stays un-delivered, so the NACK round-trip re-requests
# it — corruption costs a repair window, never correctness.
_CORRUPT_ERRORS = (ValueError, TypeError, KeyError, IndexError,
                   OverflowError, EOFError)


def _assemble(ring: BlockReceiveRing, receiver) -> tuple[bool, bool]:
    """Decode a completed ring's chunk and hand it to ``receiver``:
    ``(accepted, done)``.  Not accepted when the arena did not decode or
    the chunk failed its CRC / geometry checks; ``done`` is what
    ``receive_chunk`` returned (the receiver's model is complete)."""
    try:
        msg = FLModelChunk.from_cbor_segments(ring.segments())
        return True, receiver.receive_chunk(msg)
    except _CORRUPT_ERRORS:
        return False, False


def _deliver(by_client: dict[int, UplinkSession], frame,
             on_complete) -> None:
    """Route one released frame into its session's per-chunk reorder-aware
    ring; decode + hand the chunk to the receiver once the ring closes."""
    sess = by_client.get(frame.client)
    if sess is None or frame.chunk_index in sess.delivered_chunks:
        return                       # late duplicate of a finished chunk
    ring = sess.rings.get(frame.chunk_index)
    if ring is None:
        ring = sess.rings[frame.chunk_index] = BlockReceiveRing()
    ring.feed(frame.msg)             # slots by Block1 NUM; dups dropped
    if not ring.complete:
        return                       # gap: wait for repair to fill it
    del sess.rings[frame.chunk_index]   # a garbage arena is dropped whole
    with obs.span(obs.ASSEMBLE):
        ok, done = _assemble(ring, sess.receiver)
    if not ok:
        sess.report.corrupt_chunks += 1
        return                       # not delivered => NACK re-requests it
    sess.delivered_chunks.add(frame.chunk_index)
    if done and not sess.assembled:
        sess.assembled = True
        if on_complete is not None:
            on_complete(sess)


def _window_feedback(medium: SharedMedium, s: UplinkSession,
                     record, *, backoff=None, faults=None) -> None:
    """Window boundary: account the data window, run the NACK/ACK
    round-trip over the medium, and stage the next window (or finish)."""
    w = s._window_stats
    if record is not None and (w.frames or w.messages):
        record("FL_Model_Chunk", w)
    medium.stats.messages += w.messages
    medium.stats.payload_bytes += w.payload_bytes
    s.report.stats.add(w)
    s._window_stats = TransferStats()
    fb = s.receiver.chunk_feedback(s.model_id, s.round, s.num_chunks)
    is_ack = isinstance(fb, FLChunkAck)
    if is_ack and not s.report.completed:
        s.report.completed = [0]     # ground truth: reassembly finished
    payload = fb.to_cbor()
    mtype = "FL_Chunk_Ack" if is_ack else "FL_Chunk_Nack"
    if s.validate:
        _validate(payload, mtype)
    delivered, fstats = medium.transmit_payload(
        payload, uri=s.feedback_uri, code=Code.CONTENT,
        rx_client=s.client_id)   # the client's radio listens for feedback
    if delivered and faults is not None and faults.feedback_lost(
            s.client_id, s.window):
        delivered = False        # injected: the client never heard it
    if record is not None:
        record(mtype, fstats)
    s.report.stats.add(fstats)
    s.report.control_messages += 1
    s.report.control_payload_bytes += len(payload)
    s.window += 1
    s.report.windows = s.window
    if not delivered:
        s.report.lost_feedback += 1
        s.to_send = []               # learned nothing: poll again next window
    elif is_ack:
        s.acked = True
    else:
        back = FLChunkNack.from_cbor(payload, expect_num_chunks=s.num_chunks)
        s.to_send = sorted(back.missing)  # sched-ok: once per window feedback, not per frame
    if s.finished:
        s.done_at = medium.clock
        s._frames = iter(())
        s._lookahead = None
    else:
        _enqueue_window(medium, s)
        if backoff is not None:
            # exponential medium-aware backoff before the repair window:
            # attempt number = the window about to run (1-based repairs)
            delay = backoff.delay(s.window,
                                  turnaround_s=medium.turnaround_s,
                                  loss_estimate=medium.loss_estimate())
            s.ready_at = medium.clock + (delay if s.has_frame
                                         else max(delay,
                                                  medium.turnaround_s))
        else:
            # a repair window may transmit immediately (the feedback gap
            # was already paid); an *empty* one (lost feedback) waits a
            # poll interval before asking the receiver again
            s.ready_at = (medium.clock if s.has_frame
                          else medium.clock + medium.turnaround_s)


def _medium_report(medium: SharedMedium,
                   sessions: Sequence[UplinkSession]) -> MediumReport:
    """Fold the medium's accounting into a ``MediumReport`` — shared by
    the legacy frame-scan and the event-heap scheduler so their reports
    are field-for-field comparable in the differential suite."""
    windows = {s.client_id: (s.start_at,
                             s.done_at if s.done_at is not None
                             else medium.clock)
               for s in sessions}
    energy, duty = medium.energy_report(windows)
    return MediumReport(
        airtime_s=medium.clock, busy_s=medium.busy_s, idle_s=medium.idle_s,
        per_client_done_s={s.client_id: s.done_at for s in sessions},
        stats=medium.stats,
        downlink_airtime_s=medium.downlink_airtime_s,
        downlink_busy_s=medium.downlink_busy_s,
        per_client_energy_j=energy,
        duty_cycle=duty)


def _run_frame_scan(medium, sessions, by_client, *, sequential, record,
                    on_complete, deadline_s, backoff, faults) -> None:
    """The original per-frame scheduler: every slot rebuilds the active
    and contender lists by scanning all sessions — O(N) per frame.  Kept
    verbatim as the differential oracle for the event-heap scheduler
    (byte-identical schedules under the default policy), and as the
    ``sequential=True`` baseline (one session at a time, strict
    back-to-back — there is no contention to schedule)."""
    while True:
        if deadline_s is not None and medium.clock >= deadline_s:
            for s in sessions:
                if not s.finished:
                    s.halt(expired=True)   # straggler: the round moved on
            break
        active = [s for s in sessions if not s.finished]
        if not active:
            break
        if sequential:
            cands = active[:1]
            if cands[0].ready_at > medium.clock:
                medium.advance_to(cands[0].ready_at)
        else:
            cands = [s for s in active if s.ready_at <= medium.clock]
            if not cands:
                t = min(s.ready_at for s in active)
                if deadline_s is not None:
                    t = min(t, deadline_s)
                medium.advance_to(t)
                continue
        s = by_client[medium.arbitrate([c.client_id for c in cands],
                                       sessions=cands)]
        if s.crash_due():
            s.halt()                 # injected client crash, mid-upload
            continue
        if s.has_frame:
            frame = s._lookahead
            s._advance()
            s._frames_in_window += 1
            for fr in medium.transmit(frame, s._window_stats,
                                      drop=s._forced.get(frame.chunk_index)):
                _deliver(by_client, fr, on_complete)
            if not s.has_frame:
                # window boundary: release this client's jittered
                # stragglers (its feedback logically follows every frame
                # of the window), then gate the feedback behind the
                # receiver's turnaround — reassembly checks + response
                # guard time.  THIS is the gap interleaving reclaims:
                # sequential schedules idle through it, concurrent ones
                # fill it with other clients' frames.
                for fr in medium.flush(s.client_id):
                    _deliver(by_client, fr, on_complete)
                s.ready_at = medium.clock + medium.turnaround_s
        else:
            _window_feedback(medium, s, record,   # turnaround passed
                             backoff=backoff, faults=faults)


def _run_event_heap(medium, sessions, by_client, *, record, on_complete,
                    deadline_s, backoff, faults, sched_trace) -> None:
    """Event-heap virtual clock: the scheduler that makes 1k–10k-client
    rounds a bench row instead of a timeout.

    Every unfinished session lives in exactly one of two structures:

      * ``ready``   — session indices whose turnaround gate has passed
        (``ready_at <= clock``), kept sorted so positions map onto session
        insertion order — the same contender order the frame scan built;
      * ``waiting`` — a heap of ``(ready_at, index)``: sessions gated on
        turnaround expiry, backoff delay, or training finish.

    Each slot pops work in O(log N): drain newly-due sessions from the
    heap, grant one ready session a frame (the arbitration policy picks by
    *position*, so the default seeded draw never materializes a contender
    list), and when nobody is ready jump the clock straight to the next
    event — idle gaps cost one ``advance_to``, not a scan per frame.
    Schedules are byte-identical to ``_run_frame_scan`` under the default
    policy: same contender order, same RNG draw per contended slot, same
    deadline/crash/feedback sequencing (pinned by the differential suite).

    ``sched_trace(event, client)`` observes every scheduler transition
    (wake/grant/frame_sent/window_gap/.../expire) for the SCHEDULER state
    machine's conformance check; ``None`` costs nothing.
    """
    ready: list[int] = []            # session indices, sorted
    waiting: list[tuple[float, int]] = []
    for i, s in enumerate(sessions):
        if not s.finished:
            heapq.heappush(waiting, (s.ready_at, i))

    def _slot(i: int, s: UplinkSession) -> None:
        """Re-file an unfinished session after its ready_at moved."""
        if s.ready_at <= medium.clock:
            insort(ready, i)
        else:
            heapq.heappush(waiting, (s.ready_at, i))

    while True:
        while waiting and waiting[0][0] <= medium.clock:
            _, i = heapq.heappop(waiting)
            insort(ready, i)
            if sched_trace is not None:
                sched_trace("wake", sessions[i].client_id)
        if deadline_s is not None and medium.clock >= deadline_s:
            for s in sessions:
                if not s.finished:
                    s.halt(expired=True)   # straggler: the round moved on
                    if sched_trace is not None:
                        sched_trace("expire", s.client_id)
            break
        if not ready:
            if not waiting:
                break                # every session finished
            t = waiting[0][0]
            if deadline_s is not None:
                t = min(t, deadline_s)
            medium.advance_to(t)     # idle gap: one jump, no scanning
            continue
        if len(ready) == 1:
            k = 0                    # lone contender: no policy, no draw
        else:
            k = medium.arbitration.pick(
                medium, len(ready), lambda i: sessions[ready[i]])
        idx = ready[k]
        s = sessions[idx]
        if sched_trace is not None:
            sched_trace("grant", s.client_id)
        if s.crash_due():
            s.halt()                 # injected client crash, mid-upload
            del ready[k]
            if sched_trace is not None:
                sched_trace("crash", s.client_id)
            continue
        if s.has_frame:
            frame = s._lookahead
            s._advance()
            s._frames_in_window += 1
            for fr in medium.transmit(frame, s._window_stats,
                                      drop=s._forced.get(frame.chunk_index)):
                _deliver(by_client, fr, on_complete)
            if not s.has_frame:
                # window boundary (see _run_frame_scan): flush this
                # client's jittered stragglers, then gate its feedback
                # behind the turnaround — the gap other clients fill
                for fr in medium.flush(s.client_id):
                    _deliver(by_client, fr, on_complete)
                s.ready_at = medium.clock + medium.turnaround_s
                del ready[k]
                _slot(idx, s)
                if sched_trace is not None:
                    sched_trace("window_gap" if s.ready_at > medium.clock
                                else "window_open", s.client_id)
            elif sched_trace is not None:
                sched_trace("frame_sent", s.client_id)
        else:
            _window_feedback(medium, s, record,   # turnaround passed
                             backoff=backoff, faults=faults)
            del ready[k]
            if s.finished:
                if sched_trace is not None:
                    sched_trace("finish", s.client_id)
            else:
                _slot(idx, s)
                if sched_trace is not None:
                    sched_trace("feedback_wait" if s.ready_at > medium.clock
                                else "feedback_ready", s.client_id)


def run_interleaved_uplinks(
    medium: SharedMedium,
    sessions: Sequence[UplinkSession],
    *,
    sequential: bool = False,
    record: Callable[[str, TransferStats], None] | None = None,
    on_complete: Callable[[UplinkSession], None] | None = None,
    deadline_s: float | None = None,
    backoff=None,
    faults=None,
    legacy: bool = False,
    sched_trace: Callable[[str, int], None] | None = None,
) -> MediumReport:
    """Drive many clients' selective-repeat uplinks over one shared medium.

    ``sequential=False`` (the point of this scheduler): every session
    whose turnaround gate has passed contends for each frame slot, so one
    client's feedback gap is filled with another client's frames — round
    airtime approaches the busy floor (total frames on air) instead of
    busy + every gap serialized.  Scheduling runs on an event-heap virtual
    clock (``_run_event_heap``): O(log N) per slot, so 1,000–10,000
    concurrent clients per round is a bench row (``benchmarks/
    bench_scale.py``), not a timeout.  ``legacy=True`` keeps the original
    per-frame scan (``_run_frame_scan``) as the differential oracle — the
    two produce byte-identical schedules under the default arbitration
    policy.  ``sequential=True`` runs one session at a time (strict
    back-to-back), the baseline the airtime win is measured against;
    there is no contention to schedule, so it uses the scan loop.

    ``on_complete(session)`` fires the moment a session's receiver
    finishes reassembly — mid-schedule — which is what lets the server
    fold each model into the running aggregate and recycle the gather
    buffer while other clients are still transmitting.

    Round-lifecycle hooks (fl.round): ``deadline_s`` is the round deadline
    on the medium clock — sessions unfinished at that instant are marked
    ``expired`` (stragglers) and stop transmitting; ``backoff`` delays
    repair windows (see ``_window_feedback``); ``faults`` injects feedback
    loss, and sessions carry their own ``crash_at`` points.  Session
    ``start_at`` gates when a client becomes ready at all (its training
    finish time), so uploads begin staggered, not all at clock zero.

    ``sched_trace(event, client)`` (event-heap path only) observes every
    scheduler transition for ``analysis.statemachine``'s SCHEDULER
    conformance check.
    """
    sessions = list(sessions)
    by_client: dict[int, UplinkSession] = {}
    for s in sessions:
        if s.client_id in by_client:
            raise ValueError(f"duplicate session client id {s.client_id}")
        by_client[s.client_id] = s
    for s in sessions:
        s.ready_at = max(medium.clock, s.start_at)
        _enqueue_window(medium, s)
    if legacy or sequential:
        _run_frame_scan(medium, sessions, by_client, sequential=sequential,
                        record=record, on_complete=on_complete,
                        deadline_s=deadline_s, backoff=backoff, faults=faults)
    else:
        _run_event_heap(medium, sessions, by_client, record=record,
                        on_complete=on_complete, deadline_s=deadline_s,
                        backoff=backoff, faults=faults,
                        sched_trace=sched_trace)
    for fr in medium.flush():      # post-ACK jitter releases: late dups
        _deliver(by_client, fr, on_complete)
    return _medium_report(medium, sessions)


class AssemblerReceiver:
    """Minimal receiver endpoint: a bare ``ChunkAssembler`` plus the
    assembled result — what the loss-sweep harness and the server's uplink
    reassembly use.  ``expected_elems`` is the model size the receiver
    vouches for (bounds the gather allocation against forged geometry)."""

    def __init__(self, *, expected_elems: int | None = None,
                 pool: GatherBufferPool | None = None) -> None:
        self.assembler = ChunkAssembler(expected_elems=expected_elems,
                                        pool=pool)
        self.assembled: np.ndarray | None = None

    def receive_chunk(self, msg: FLModelChunk) -> bool:
        flat = self.assembler.add(msg)
        if flat is None:
            return False
        self.assembled = flat
        return True

    def chunk_feedback(self, model_id: uuid.UUID, round_: int,
                       num_chunks: int) -> FLChunkAck | FLChunkNack:
        return self.assembler.feedback(model_id, round_, num_chunks)
