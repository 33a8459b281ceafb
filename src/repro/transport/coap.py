"""CoAP message layer (RFC 7252) + blockwise transfer (RFC 7959).

Enough of CoAP is implemented to account *exact* on-the-wire bytes for the
paper's scenario (§IV): CON/NON/ACK messages, options (Uri-Path, Observe,
Block1/Block2, Content-Format), payload marker, and blockwise splitting so
that every frame fits the IEEE 802.15.4 127-byte MTU.  This is what turns
the paper's Table-I message sizes into frame counts on the simulated link
(§VI-B "message interval" analysis).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from enum import IntEnum

IEEE802154_MTU = 127
# 802.15.4 MAC header+FCS (~21 B) + 6LoWPAN/UDP compressed header (~11 B)
LOWPAN_OVERHEAD = 32
COAP_MAX_PAYLOAD = 64  # payload per block so header+token+options fit the MTU

CONTENT_CBOR = 60  # application/cbor (RFC 7049 registry)


class Code(IntEnum):
    EMPTY = 0x00
    GET = 0x01
    POST = 0x02
    PUT = 0x03
    CONTENT = 0x45      # 2.05
    CHANGED = 0x44      # 2.04
    ACK_TIMEOUT = 0xA0  # internal


class Type(IntEnum):
    CON = 0
    NON = 1
    ACK = 2
    RST = 3


class Option(IntEnum):
    OBSERVE = 6
    URI_PATH = 11
    CONTENT_FORMAT = 12
    URI_QUERY = 15
    BLOCK2 = 23
    BLOCK1 = 27


@dataclass
class CoapMessage:
    mtype: Type
    code: Code
    mid: int
    token: bytes = b""
    options: list[tuple[int, bytes]] = field(default_factory=list)
    payload: bytes = b""

    def encode(self) -> bytes:
        """RFC 7252 §3 wire format."""
        if len(self.token) > 8:
            raise ValueError("token too long")
        out = bytearray()
        out.append((1 << 6) | (self.mtype << 4) | len(self.token))
        out.append(self.code)
        out += self.mid.to_bytes(2, "big")
        out += self.token
        prev = 0
        for num, val in sorted(self.options):
            delta = num - prev
            prev = num
            d, dx = self._nibble(delta)
            l, lx = self._nibble(len(val))
            out.append((d << 4) | l)
            out += dx + lx + val
        if self.payload:
            out.append(0xFF)
            out += self.payload
        return bytes(out)  # copy-ok: header finalize — freeze the built frame

    @staticmethod
    def _nibble(v: int) -> tuple[int, bytes]:
        if v < 13:
            return v, b""
        if v < 269:
            return 13, bytes([v - 13])  # copy-ok: 1-byte option-extension constant, not a buffer copy
        return 14, (v - 269).to_bytes(2, "big")

    @classmethod
    def decode(cls, data: bytes) -> "CoapMessage":
        ver_t_tkl, code = data[0], data[1]
        mtype = Type((ver_t_tkl >> 4) & 3)
        tkl = ver_t_tkl & 0xF
        mid = int.from_bytes(data[2:4], "big")
        token = data[4:4 + tkl]
        pos = 4 + tkl
        options: list[tuple[int, bytes]] = []
        num = 0
        while pos < len(data):
            if data[pos] == 0xFF:
                pos += 1
                break
            d, l = data[pos] >> 4, data[pos] & 0xF
            pos += 1
            d, pos = cls._read_ext(d, data, pos)
            l, pos = cls._read_ext(l, data, pos)
            num += d
            options.append((num, data[pos:pos + l]))
            pos += l
        return cls(mtype, Code(code), mid, token, options, data[pos:])

    @staticmethod
    def _read_ext(v: int, data: bytes, pos: int) -> tuple[int, int]:
        if v == 13:
            return data[pos] + 13, pos + 1
        if v == 14:
            return int.from_bytes(data[pos:pos + 2], "big") + 269, pos + 2
        if v == 15:
            raise ValueError("reserved option nibble")
        return v, pos


def block_option_value(num: int, more: bool, szx: int) -> bytes:
    """RFC 7959 block option uint: NUM << 4 | M << 3 | SZX."""
    v = (num << 4) | (int(more) << 3) | szx
    if v == 0:
        return b""
    length = max(1, math.ceil(v.bit_length() / 8))
    return v.to_bytes(length, "big")


# every blockwise frame carries 64 B blocks (SZX 2: 2**(SZX + 4) bytes)
# under one 1-byte token
BLOCK_SZX = COAP_MAX_PAYLOAD.bit_length() - 5
BLOCK_TOKEN = b"\x01"


class BlockwiseFraming:
    """Header template of one blockwise transfer: what every Block1 frame
    of a URI and code shares, worked out once, so that a frame's encoded
    length is arithmetic on its NUM, M bit and block length.

    A frame is the 4-byte CoAP header and token, the URI-Path and
    Content-Format options (the same for every block), the Block1 option
    (absent from a single-block transfer), then the ``0xFF`` marker and
    the block.  ``message`` builds the full ``CoapMessage`` for a caller
    that needs one; ``blocks`` slices and measures, building nothing."""

    __slots__ = ("code", "_head", "_base")

    def __init__(self, *, uri: str, code: Code = Code.POST) -> None:
        self.code = code
        self._head = tuple(
            [(Option.URI_PATH, seg.encode())
             for seg in uri.strip("/").split("/")]
            + [(Option.CONTENT_FORMAT, bytes([CONTENT_CBOR]))])  # copy-ok: 1-byte content-format constant, not a buffer copy
        self._base = len(CoapMessage(Type.CON, code, 0, BLOCK_TOKEN,
                                     list(self._head)).encode())

    def wire_bytes(self, num: int, more: bool, nbytes: int) -> int:
        """Encoded length of block ``num`` carrying ``nbytes``.  Block1
        follows Content-Format (12 -> 27): a delta of 15 always takes the
        one-byte extension, so the option is 2 bytes plus its 1-3 byte
        value (1 below NUM 16, 2 below 4096, 3 above)."""
        n = self._base
        if num or more:
            n += 2 + (((num << 4) | (more << 3) | BLOCK_SZX).bit_length()
                      + 7) // 8
        if nbytes:
            n += 1 + nbytes
        return n

    def message(self, block, num: int, more: bool) -> CoapMessage:
        """Block ``num``'s full CON message, as sent on the air."""
        opts = list(self._head)
        if num or more:
            opts.append((Option.BLOCK1, block_option_value(num, more,
                                                           BLOCK_SZX)))
        return CoapMessage(Type.CON, self.code, num, BLOCK_TOKEN, opts,
                           block)

    def blocks(self, payload):
        """Yield ``(num, more, block, wire_bytes)`` for every frame of
        ``payload`` in order, one block at a time.

        ``payload`` is anything with ``len()`` and contiguous slicing —
        ``bytes``, a buffer, or a ``ScatterPayload`` over vectored
        segments: a multi-MB vectored payload is sliced ≤64 B per step and
        never joined.  A frame over the 127 B MTU raises."""
        size = len(payload)
        last = max(1, -(-size // COAP_MAX_PAYLOAD)) - 1
        wire_bytes = self.wire_bytes
        for num in range(last + 1):
            lo = num * COAP_MAX_PAYLOAD
            more = num < last
            wire = wire_bytes(num, more, min(COAP_MAX_PAYLOAD, size - lo))
            if wire + LOWPAN_OVERHEAD > IEEE802154_MTU:
                raise AssertionError(
                    f"CoAP message exceeds MTU: {wire + LOWPAN_OVERHEAD}")
            yield num, more, payload[lo:lo + COAP_MAX_PAYLOAD], wire


def iter_blockwise_messages(payload, *, uri: str, code: Code = Code.POST):
    """Lazily split a payload into the Block1 CoAP messages that carry it
    (``BlockwiseFraming.blocks``, each built into its message)."""
    framing = BlockwiseFraming(uri=uri, code=code)
    for num, more, block, _ in framing.blocks(payload):
        yield framing.message(block, num, more)


def blockwise_messages(payload, *, uri: str,
                       code: Code = Code.POST) -> list[CoapMessage]:
    """Eager form of ``iter_blockwise_messages`` (materializes the list)."""
    return list(iter_blockwise_messages(payload, uri=uri, code=code))


# RFC 7959 §2.2: the block NUM field is at most 20 bits wide.  A frame
# claiming a larger NUM is malformed, and — since out-of-order NUMs size
# receiver state — the bound also caps what a hostile frame can make the
# ring hold.
MAX_BLOCK_NUM = 1 << 20
# Out-of-order blocks parked past the contiguous prefix.  Real reorder is
# a few frames of jitter; thousands of parked blocks means the stream is
# garbage (or hostile), not late.
MAX_PENDING_BLOCKS = 1 << 14


class BlockReceiveRing:
    """Receive-side segment ring: blockwise payloads reassembled into
    *arena segments*, never joined on top of.

    Two arrival models share the ring:

    * ``add_block(payload)`` — legacy in-order append: each delivered
      ≤64 B block's payload is appended in arrival order.  Consecutive
      blocks coalesce into a growing ``bytearray`` arena — copying each
      block into the arena *is* the receiver-ownership copy the wire hop
      costs, paid once per byte, block-granular.
    * ``add_block(payload, num=...)`` / ``feed(msg)`` — *reorder-aware*
      slotting by the Block1 NUM: blocks may arrive in any order, with
      duplicates (counted and dropped — a NACK-repaired chunk re-sends
      every block, including ones that already landed) and gaps (parked
      out-of-order blocks wait in a bounded pending map until the missing
      NUMs fill them in).  The contiguous prefix coalesces into the same
      arena as the in-order path, so an in-order stream costs exactly
      what it always did, and a reordered one pays only O(jitter window)
      extra transient references.

    Either way the ring hands the decode layer its arena segments as-is:
    ``fastpath.decode`` / ``from_cbor_segments`` walk them with a segment
    cursor, and a payload that landed inside one arena (the common case —
    an uninterrupted block run) decodes as a *borrowed* zero-copy view of
    the ring's own memory.  No contiguous join is ever layered on top.

    Reading ``segments()`` seals the current arena (a ``bytearray`` with
    exported views must not grow), so in append mode later blocks simply
    start a new arena segment.  In slotted mode ``segments()`` requires
    the transfer to be ``complete`` — decoding around a gap would yield
    garbage — and raises ``ValueError`` otherwise.
    """

    __slots__ = ("_segments", "_arena", "_num_blocks", "_nbytes",
                 "_slotted", "_pending", "_next_num", "_last_num",
                 "duplicates")

    def __init__(self) -> None:
        self._segments: list = []
        self._arena: bytearray | None = None
        self._num_blocks = 0
        self._nbytes = 0
        self._slotted: bool | None = None   # None until the first block
        self._pending: dict[int, bytes] = {}
        self._next_num = 0                  # slotted: next NUM to coalesce
        self._last_num: int | None = None   # slotted: NUM with more=False
        self.duplicates = 0

    # -- shared arena append --------------------------------------------------

    def _append(self, payload, nbytes: int) -> None:
        if self._arena is None:
            self._arena = bytearray()
            self._segments.append(self._arena)
        self._arena += payload
        self._num_blocks += 1
        self._nbytes += nbytes

    def _set_mode(self, slotted: bool) -> None:
        if self._slotted is None:
            self._slotted = slotted
        elif self._slotted != slotted:
            raise ValueError(
                "BlockReceiveRing cannot mix in-order appends and "
                "NUM-slotted blocks in one transfer")

    # -- arrival paths --------------------------------------------------------

    def add_block(self, payload, num: int | None = None, *,
                  last: bool = False) -> None:
        """Deliver one block's payload (``bytes`` or any buffer).

        ``num=None`` keeps the legacy append-in-arrival-order semantics.
        With ``num`` the block is slotted by its Block1 NUM: duplicates are
        dropped (counted), gaps are tolerated until later arrivals — e.g.
        a NACK-repair re-send — fill them.  ``last=True`` marks the final
        block of the transfer (Block1 ``M`` bit clear), fixing the total.
        """
        n = payload.nbytes if isinstance(payload, memoryview) else len(payload)
        if num is None:
            self._set_mode(False)
            if not n:
                return
            self._append(payload, n)
            return
        self._set_mode(True)
        if not 0 <= num < MAX_BLOCK_NUM:
            raise ValueError(f"block NUM {num} out of range")
        if self._last_num is not None:
            if last and num != self._last_num:
                raise ValueError(
                    f"conflicting final block: NUM {num} after "
                    f"{self._last_num}")
            if num > self._last_num:
                raise ValueError(
                    f"block NUM {num} beyond final block {self._last_num}")
        if last:
            if (self._pending and max(self._pending) > num) or \
                    self._next_num > num + 1:
                raise ValueError(
                    f"final block NUM {num} below an already-received block")
            self._last_num = num
        if num < self._next_num or num in self._pending:
            self.duplicates += 1
            return
        if num == self._next_num:
            if self._arena is None and self._segments:
                # segments() sealed the arena; only possible once complete,
                # so any further non-duplicate NUM is a protocol violation
                raise ValueError("slotted ring grew after it was sealed")
            self._append(payload, n)
            self._next_num += 1
            while self._next_num in self._pending:
                nxt = self._pending.pop(self._next_num)
                self._append(nxt, len(nxt))
                self._next_num += 1
        else:
            if len(self._pending) >= MAX_PENDING_BLOCKS:
                raise ValueError(
                    f"more than {MAX_PENDING_BLOCKS} out-of-order blocks "
                    "parked; dropping the transfer")
            # park one owned copy: the frame buffer may be reused by the
            # link once this call returns
            self._pending[num] = bytes(payload)  # copy-ok: parked block must outlive the reusable link buffer

    def feed(self, msg: "CoapMessage") -> None:
        """Deliver one received blockwise CoAP message, slotting its
        payload by the Block1 NUM (reorder-aware).  A message without a
        Block1 option is a complete single-block transfer."""
        num, more = 0, False
        for onum, val in msg.options:
            if onum == Option.BLOCK1:
                v = int.from_bytes(val, "big")
                num, more = v >> 4, bool(v & 0x08)
                break
        self.add_block(msg.payload, num=num, last=not more)

    # -- reassembly state -----------------------------------------------------

    @property
    def complete(self) -> bool:
        """True when every block of a slotted transfer has arrived (the
        final block is known and the contiguous prefix covers it).  An
        append-mode ring has no gap concept and is always complete."""
        if not self._slotted:
            return True
        return self._last_num is not None and self._next_num > self._last_num

    def missing_nums(self) -> list[int]:
        """Block NUMs known to be missing: gaps below the highest block
        seen (and below the final block, once known).  An unknown tail —
        nothing received past the last contiguous block and no final block
        yet — reports as no *known* gaps."""
        if not self._slotted:
            return []
        upper = self._last_num
        if upper is None:
            upper = max(self._pending, default=self._next_num - 1)
        return [n for n in range(self._next_num, upper + 1)
                if n not in self._pending]

    def segments(self) -> list:
        if self._slotted and not self.complete:
            raise ValueError(
                f"incomplete blockwise transfer: missing {self.missing_nums()}")
        segs = [memoryview(s).toreadonly() if isinstance(s, bytearray) else s
                for s in self._segments]
        self._arena = None  # seal: exported views pin the arena's size
        return segs

    @property
    def num_blocks(self) -> int:
        return self._num_blocks

    def __len__(self) -> int:
        return self._nbytes

    def tobytes(self) -> bytes:
        """Explicit contiguous join — for tests/diagnostics, not the hot
        path (decode consumes the ring segment-wise)."""
        # join() accepts the memoryview segments directly — materialising
        # each one first paid a second, redundant copy per segment
        return b"".join(self._segments)  # copy-ok: diagnostics-only contiguous dump

    def clear(self) -> None:
        self._segments.clear()
        self._arena = None
        self._num_blocks = 0
        self._nbytes = 0
        self._slotted = None
        self._pending.clear()
        self._next_num = 0
        self._last_num = None
        self.duplicates = 0


@dataclass
class TransferStats:
    messages: int = 0          # application payloads
    blocks: int = 0            # blockwise CoAP messages
    frames: int = 0            # link frames incl. retransmissions
    payload_bytes: int = 0
    wire_bytes: int = 0        # CoAP bytes incl. headers
    link_bytes: int = 0        # + MAC/6LoWPAN overhead per frame
    retransmissions: int = 0
    failed_messages: int = 0   # gave up after MAX_RETRANSMIT

    def add(self, other: "TransferStats") -> None:
        for f in ("messages", "blocks", "frames", "payload_bytes",
                  "wire_bytes", "link_bytes", "retransmissions",
                  "failed_messages"):
            setattr(self, f, getattr(self, f) + getattr(other, f))


def transfer_stats(payload, *, uri: str,
                   code: Code = Code.POST) -> TransferStats:
    """Frame accounting for one application payload over the 127 B link."""
    stats = TransferStats(messages=1, payload_bytes=len(payload))
    for _, _, _, wire in BlockwiseFraming(uri=uri, code=code).blocks(payload):
        stats.blocks += 1
        stats.frames += 1
        stats.wire_bytes += wire
        stats.link_bytes += wire + LOWPAN_OVERHEAD
    return stats
