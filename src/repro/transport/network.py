"""Simulated low-power lossy network (IEEE 802.15.4-like).

Deterministic (seeded) frame-level simulation: per-frame drop probability,
CON retransmission with exponential backoff (RFC 7252 §4.2), 250 kbit/s link
rate for latency accounting.  The FL runtime sends every TinyFL message
through this to report bytes / frames / retransmissions / airtime per round.

Two delivery models coexist (docs/chunk_protocol.md):

  * ``send_payload`` — CON unicast: every frame is acknowledged and
    retransmitted up to MAX_RETRANSMIT; a payload either arrives whole or is
    declared failed.  Used for small control messages and monolithic model
    transfers.  ``deliver_payload`` is the same transfer with the receive
    side attached: delivered blocks land in a ``BlockReceiveRing`` the
    decode layer consumes segment-wise (never joined).
  * ``request_stream`` — one selective-repeat *window*: a batch of chunk
    payloads pushed NON-style with per-payload delivery tracking instead of
    an all-or-nothing verdict.  Losing a chunk never aborts the window; the
    caller learns exactly which indices each receiver got and drives the
    NACK round-trip (re-sending only the missing set) on top.
  * ``iter_tagged_frames`` — the async-style *multiplexed* face of
    ``request_stream``: instead of transmitting a window inline, its frames
    are handed out one at a time, each tagged (client, window, chunk-index,
    Block1 NUM), to a shared-medium scheduler
    (``transport.medium.SharedMedium``) that owns *when* each frame goes on
    the air.  Many clients' windows then interleave frame-by-frame in one
    contention domain instead of running back-to-back, and the receive side
    slots blocks by NUM (reorder-aware ``BlockReceiveRing``).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np

from repro.core.fastpath import ScatterPayload
from repro.transport.coap import (
    LOWPAN_OVERHEAD,
    BlockReceiveRing,
    BlockwiseFraming,
    CoapMessage,
    Code,
    TransferStats,
)

LINK_BPS = 250_000
MAX_RETRANSMIT = 4

# Test hook signature: (uri, window, chunk_index, receiver) -> drop whole chunk?
ChunkDropFn = Callable[[str, int, int, int], bool]


def as_wire_payload(payload):
    """Normalize a payload for the link: bytes and buffers pass through; a
    vectored segment list (``encode_vectored`` output) is wrapped in a
    ``ScatterPayload`` so byte counting and blockwise framing work without
    ever joining the segments."""
    if isinstance(payload, (list, tuple)):
        return ScatterPayload(payload)
    return payload


def con_blockwise_transfer(payload, *, uri: str, code: Code,
                           drop: Callable[[], bool],
                           on_frame: Callable[[int], None] | None = None,
                           ring: "BlockReceiveRing | None" = None
                           ) -> TransferStats:
    """The one CON blockwise-transfer loop: per-frame ack + retransmission
    up to MAX_RETRANSMIT, exact byte/frame accounting, optional delivery
    into a receive ring.  ``drop()`` decides each attempt's fate (the
    caller owns the RNG — ``LossyLink`` and ``SharedMedium`` share this
    loop so their accounting can never diverge); ``on_frame(wire_bytes)``
    fires once per attempt for callers that track airtime on a clock.
    A frame still lost after MAX_RETRANSMIT marks the whole payload
    undelivered (``failed_messages`` = 1) and aborts the transfer."""
    payload = as_wire_payload(payload)
    stats = TransferStats(messages=1, payload_bytes=len(payload))
    for num, more, block, wire in BlockwiseFraming(
            uri=uri, code=code).blocks(payload):
        frame = wire + LOWPAN_OVERHEAD
        stats.blocks += 1
        attempts = 0
        while True:
            attempts += 1
            stats.frames += 1
            stats.wire_bytes += wire
            stats.link_bytes += frame
            if on_frame is not None:
                on_frame(wire)
            if not drop():
                break
            if attempts > MAX_RETRANSMIT:
                stats.failed_messages = 1
                return stats
            stats.retransmissions += 1
        if ring is not None:
            ring.add_block(block, num=num, last=not more)
    return stats


@dataclass
class StreamDelivery:
    """Result of one ``request_stream`` window."""

    stats: TransferStats
    delivered: list[set[int]]    # per receiver: chunk indices that arrived


class TaggedFrame:
    """One link frame of a multiplexed chunk window.

    The tag (client, window, chunk_index, block_num) is what lets frames
    from many concurrent uplinks share one contention domain: the medium
    arbitrates and reorders *frames*, and the receive side routes each one
    to the right client's per-chunk reorder-aware ring by its tag, slotting
    ``payload`` into the arena by ``block_num`` (``more`` clear on the
    final block).  ``wire_bytes`` is the encoded CoAP size (MAC/6LoWPAN
    overhead extra), computed by ``BlockwiseFraming``.

    ``msg`` is the full CoAP message, built from ``framing`` only when a
    caller reads it: it encodes to exactly ``wire_bytes`` bytes.  Each
    build counts as ``frames_built`` in the round's record.
    """

    __slots__ = ("client", "window", "chunk_index", "block_num", "_msg",
                 "wire_bytes", "payload", "more", "framing")

    def __init__(self, client: int, window: int, chunk_index: int,
                 block_num: int, msg: CoapMessage | None = None,
                 wire_bytes: int = 0, payload=b"", more: bool = False,
                 framing: BlockwiseFraming | None = None) -> None:
        self.client = client
        self.window = window
        self.chunk_index = chunk_index
        self.block_num = block_num
        self._msg = msg
        self.wire_bytes = wire_bytes
        self.payload = payload
        self.more = more
        self.framing = framing

    @property
    def msg(self) -> CoapMessage:
        if self._msg is None:
            # off the fast path; imported here so transport stays jax-free
            from repro import obs
            obs.count("frames_built", 1)
            self._msg = self.framing.message(self.payload, self.block_num,
                                             self.more)
        return self._msg

    def with_payload(self, payload) -> "TaggedFrame":
        """This frame carrying ``payload`` instead (a damaged copy): same
        tag and ``wire_bytes``, its message built with the new payload."""
        return TaggedFrame(self.client, self.window, self.chunk_index,
                           self.block_num, replace(self.msg, payload=payload),
                           self.wire_bytes, payload, self.more, self.framing)


# The ``client`` tag of a downlink (server -> cohort) frame.  Downlink
# frames share the uplink's TaggedFrame shape so one SharedMedium carries
# both directions on one clock; the sentinel keeps them out of any
# client-keyed uplink routing, and per-receiver delivery verdicts are keyed
# by the *receiving* client's id instead (SharedMedium.transmit_downlink).
DOWNLINK_CLIENT = -1


def iter_downlink_frames(payloads: Sequence, *, uri: str, window: int,
                         indices: Sequence[int] | None = None,
                         code: Code = Code.POST) -> Iterator[TaggedFrame]:
    """``iter_tagged_frames`` for the server's multicast dissemination:
    one lazily-framed chunk window tagged ``DOWNLINK_CLIENT``, transmitted
    once per frame however many receivers listen."""
    return iter_tagged_frames(payloads, uri=uri, client=DOWNLINK_CLIENT,
                              window=window, indices=indices, code=code)


def iter_tagged_frames(payloads: Sequence, *, uri: str, client: int,
                       window: int, indices: Sequence[int] | None = None,
                       code: Code = Code.POST) -> Iterator[TaggedFrame]:
    """Lazily frame one selective-repeat window for a shared medium.

    Yields every blockwise CoAP frame of every chunk payload in order,
    tagged (client, window, chunk-index, Block1 NUM).  One frame exists at
    a time — a repair window over a multi-MB model costs O(block)
    transient memory, exactly like the inline ``request_stream`` path.
    """
    framing = BlockwiseFraming(uri=uri, code=code)
    if indices is None:
        indices = range(len(payloads))
    for payload, idx in zip(payloads, indices):
        for num, more, block, wire in framing.blocks(
                as_wire_payload(payload)):
            yield TaggedFrame(client, window, idx, num, None, wire, block,
                              more, framing)


@dataclass
class LossyLink:
    drop_prob: float = 0.0
    seed: int = 0
    # When set, chunk-level loss in ``request_stream`` is decided by this
    # schedule instead of the frame-level RNG — the loss-sweep harness uses
    # it to inject exact seeded drop patterns (uniform / bursty /
    # adversarial) while byte accounting stays realistic.
    chunk_drop: ChunkDropFn | None = None
    # Optional fault schedule (fl.faults.FaultPlan shape — duck-typed to
    # keep transport free of fl imports): blackout intervals on the round
    # clock force frame loss on top of the RNG.
    faults: object | None = None
    _rng: np.random.Generator = field(init=False, repr=False)
    # Virtual link clock: every frame that crosses (either direction, CON
    # retries included) advances it by its airtime, so the FL round engine
    # can evaluate deadlines on transport time instead of wall time.
    clock_s: float = field(init=False, default=0.0, repr=False)
    _round_t0: float = field(init=False, default=0.0, repr=False)

    def __post_init__(self) -> None:
        self._rng = np.random.default_rng(self.seed)

    # -- virtual clock (round-relative) --------------------------------------

    def _tick(self, wire: int) -> None:
        self.clock_s += (wire + LOWPAN_OVERHEAD) * 8 / LINK_BPS

    def mark_round_start(self) -> None:
        """Zero the round-relative clock (deadlines are per round)."""
        self._round_t0 = self.clock_s

    @property
    def round_clock_s(self) -> float:
        return self.clock_s - self._round_t0

    def advance_to_round(self, t: float) -> None:
        """Advance to round-relative instant ``t`` (idle: a client not yet
        ready, or a backoff delay). Never moves the clock backwards."""
        if t > self.round_clock_s:
            self.clock_s = self._round_t0 + t

    def advance(self, dt: float) -> None:
        if dt > 0:
            self.clock_s += dt

    def _frame_lost(self) -> bool:
        # RNG draw first, unconditionally: threading a blackout schedule
        # through must not shift the drop stream of fault-free frames
        # (the differential recovery oracle depends on replay identity)
        lost = self._rng.random() < self.drop_prob
        if self.faults is not None and self.faults.blackout_at(
                self.round_clock_s):
            return True
        return bool(lost)

    def loss_estimate(self) -> float:
        """The link's a-priori frame-loss fraction (point-to-point links
        know their configured loss; the SharedMedium estimates from
        observed traffic instead) — feeds medium-aware backoff."""
        return self.drop_prob

    def send_payload(self, payload, *, uri: str,
                     code: Code = Code.POST) -> TransferStats:
        """Blockwise transfer with per-frame ack + retransmission.

        ``payload`` is ``bytes``, any buffer, or a vectored segment list /
        ``ScatterPayload`` — the scatter-gather forms are framed by slicing
        ≤64 B blocks out of the segment chain, so a multi-MB vectored
        message is never joined.  A frame still lost after MAX_RETRANSMIT
        marks the whole payload undelivered (``failed_messages`` = 1); the
        FL layer treats that as a client dropout for the round — no
        exception, training continues."""
        return self._blockwise_transfer(payload, uri=uri, code=code,
                                        ring=None)

    def deliver_payload(self, payload, *, uri: str, code: Code = Code.POST
                        ) -> tuple[TransferStats, BlockReceiveRing | None]:
        """``send_payload`` plus the receive side: every block that
        survives the link lands in a ``BlockReceiveRing``, the segmented
        receiver buffer the decode layer consumes directly (no contiguous
        join).  Vectored payloads thus cross end to end — sender segments
        are sliced per block (the block slice *is* the simulated wire-hop
        copy, O(block) at a time) and the receiver decodes straight out of
        its per-block buffers.  Returns ``(stats, ring)``; ``ring`` is
        None when the transfer failed after MAX_RETRANSMIT."""
        ring = BlockReceiveRing()
        stats = self._blockwise_transfer(payload, uri=uri, code=code,
                                         ring=ring)
        return stats, (None if stats.failed_messages else ring)

    def _blockwise_transfer(self, payload, *, uri: str, code: Code,
                            ring: BlockReceiveRing | None) -> TransferStats:
        return con_blockwise_transfer(
            payload, uri=uri, code=code, drop=self._frame_lost,
            on_frame=self._tick, ring=ring)

    def send_stream(self, payloads: Iterable, *, uri: str,
                    code: Code = Code.POST,
                    stop_on_failure: bool = True) -> TransferStats:
        """Send a stream of application payloads (e.g. FL model chunks).

        Payloads may be ``bytes``, any buffer, or vectored segment lists
        (``memoryview`` slices and scatter-gather output from the zero-copy
        encoder are sent without conversion or joining).  Aggregated
        ``TransferStats`` across the stream; with ``stop_on_failure`` the
        stream aborts at the first undeliverable payload — the receiver
        cannot assemble a model with a hole in it, so the remaining chunks
        would be wasted airtime.
        """
        total = TransferStats()
        for payload in payloads:
            total.add(self.send_payload(payload, uri=uri, code=code))
            if stop_on_failure and total.failed_messages:
                break
        return total

    def request_stream(self, payloads: Sequence, *, uri: str,
                       code: Code = Code.POST,
                       indices: Sequence[int] | None = None,
                       num_receivers: int = 1,
                       multicast: bool = False,
                       window: int = 0,
                       client_ids: Sequence[int] | None = None
                       ) -> StreamDelivery:
        """Send one selective-repeat window of chunk payloads.

        ``indices[i]`` names the chunk carried by ``payloads[i]`` (defaults
        to 0..n-1); repair windows pass the original chunk indices so
        delivery sets and drop schedules stay keyed by chunk identity.
        ``client_ids[r]`` maps receiver slot ``r`` to the FL client id the
        ``chunk_drop`` schedule is keyed by; without it the schedule sees
        the bare slot index — fine for ad-hoc test schedules, wrong for a
        ``FaultPlan`` (an uplink's single receiver slot is the *server*,
        and a downlink cohort's slot order is not the client id).

        * ``multicast=True``: every frame goes on the air exactly once
          (bytes counted once) and each of ``num_receivers`` receivers
          independently loses frames — a receiver holds a chunk iff it got
          every frame.  No link-layer retransmission: recovery belongs to
          the chunk layer's NACK round-trip.
        * ``multicast=False``: CON unicast per chunk (frame retransmission
          up to MAX_RETRANSMIT), but unlike ``send_payload`` streams, a
          chunk that exhausts its budget is recorded as undelivered and the
          window *continues* — no abort.

        The ``chunk_drop`` schedule, when set, replaces the frame-level RNG
        for delivery decisions (frames are still counted once for byte
        accounting), making chunk loss exactly reproducible in tests.
        """
        payloads = [as_wire_payload(p) for p in payloads]
        if indices is None:
            indices = range(len(payloads))
        delivered: list[set[int]] = [set() for _ in range(num_receivers)]
        total = TransferStats()
        for payload, idx in zip(payloads, indices):
            if self.chunk_drop is not None:
                stats = self._count_frames_once(payload, uri=uri, code=code)
                got = [not self.chunk_drop(
                           uri, window, idx,
                           client_ids[r] if client_ids is not None else r)
                       for r in range(num_receivers)]
            elif multicast:
                stats, got = self._multicast_payload(
                    payload, uri=uri, code=code, num_receivers=num_receivers)
            else:
                stats = self.send_payload(payload, uri=uri, code=code)
                got = [not stats.failed_messages] * num_receivers
                stats.failed_messages = 0  # chunk loss is recoverable here
            total.add(stats)
            for r in range(num_receivers):
                if got[r]:
                    delivered[r].add(idx)
        return StreamDelivery(stats=total, delivered=delivered)

    def _count_frames_once(self, payload, *, uri: str,
                           code: Code) -> TransferStats:
        """Byte/frame accounting for a payload framed once (no retries)."""
        stats = TransferStats(messages=1, payload_bytes=len(payload))
        for _, _, _, wire in BlockwiseFraming(uri=uri,
                                              code=code).blocks(payload):
            stats.blocks += 1
            stats.frames += 1
            stats.wire_bytes += wire
            stats.link_bytes += wire + LOWPAN_OVERHEAD
            self._tick(wire)
        return stats

    def _multicast_payload(self, payload, *, uri: str, code: Code,
                           num_receivers: int
                           ) -> tuple[TransferStats, list[bool]]:
        """NON multicast: frames on air once, per-receiver independent loss.

        The loss unit is the *chunk* (one draw per receiver per payload),
        matching the selective-repeat recovery granularity: a multi-frame
        chunk is either held whole or NACK'd whole, so simulating it as one
        loss event keeps ``drop_prob`` meaningful for multi-kB chunks
        (per-frame loss compounded over dozens of frames would make every
        chunk vanish and says nothing the chunk layer can act on).
        """
        stats = self._count_frames_once(payload, uri=uri, code=code)
        if self.drop_prob > 0.0:
            got = (self._rng.random(num_receivers) >= self.drop_prob).tolist()
        else:
            got = [True] * num_receivers
        return stats, got

    @staticmethod
    def airtime_seconds(stats: TransferStats) -> float:
        return stats.link_bytes * 8 / LINK_BPS
