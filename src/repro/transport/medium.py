"""Shared-medium airtime model: one contention domain, per-frame arbitration.

The ``LossyLink`` accounts a single point-to-point transfer; real low-power
deployments put *every* client on the same radio channel, where uplink
airtime — not per-client serialization — dominates round latency.  This
module models that shared medium:

  * **single contention domain** — exactly one frame is on the air at a
    time; the virtual clock advances by each frame's airtime, so total
    *busy* time is identical however transmissions are ordered;
  * **per-frame arbitration** — when several clients contend, a seeded RNG
    picks who transmits next (deterministic interleaving);
  * **turnaround gaps** — after a client finishes a selective-repeat
    window it must wait for feedback processing (``turnaround_s``) before
    its next window.  Sequential schedules pay every gap serially; an
    interleaved schedule fills one client's gap with another client's
    frames — that reclaimed idle time is the whole airtime win;
  * **reorder / jitter** — a delivered frame may be held back and released
    after up to ``max_reorder_lag`` later frames (seeded), exercising the
    reorder-aware receive ring;
  * **loss** — per-frame drops at ``frame_drop_prob``, or an exact
    ``chunk_drop`` schedule (same shape as ``LossyLink.chunk_drop``) for
    reproducible loss-sweep tests.

The medium knows nothing about chunks or NACKs: it transmits tagged frames
(``transport.network.TaggedFrame``) and control payloads, and accounts
clock/busy/idle.  The selective-repeat scheduling on top lives in
``fl.chunking.run_interleaved_uplinks``.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

from repro.transport.coap import LOWPAN_OVERHEAD, Code, TransferStats
from repro.transport.network import (
    LINK_BPS,
    ChunkDropFn,
    TaggedFrame,
    con_blockwise_transfer,
)


def _damage_frame(frame: TaggedFrame, kind: str) -> TaggedFrame | None:
    """Deliver a damaged copy of ``frame``: one payload byte flipped
    (``"corrupt"``) or the final payload byte lost (``"truncate"``).
    The receive path must detect it (CBOR decode / per-chunk CRC) and
    recover via NACK — never crash, never install garbage.  Returns None
    when there is no payload left to damage (degrades to a drop).  Only a
    ``FaultPlan`` verdict takes this path, so the damaged copy carries its
    full message."""
    payload = bytes(frame.payload)  # copy-ok: fault injection mutates an owned copy by design
    if not payload:
        return None
    if kind == "corrupt":
        mid = len(payload) // 2
        payload = (payload[:mid]
                   + bytes([payload[mid] ^ 0xFF])  # copy-ok: single damaged byte, not a buffer copy
                   + payload[mid + 1:])
    elif kind == "truncate":
        payload = payload[:-1]
        if not payload:
            return None
    else:
        raise ValueError(f"unknown frame damage kind {kind!r}")
    return frame.with_payload(payload)


@dataclass
class MediumReport:
    """Airtime accounting for one multi-client transfer over the medium.

    When the whole round runs on one medium (downlink dissemination +
    feedback + uplink on one clock), ``downlink_airtime_s`` /
    ``downlink_busy_s`` carve out the dissemination phase's share:
    ``airtime_s`` is then the whole round's clock and the uplink share is
    the difference — docs/concurrent_uplink.md."""

    airtime_s: float = 0.0            # virtual clock at completion
    busy_s: float = 0.0               # frames on the air
    idle_s: float = 0.0               # gaps no contender could fill
    per_client_done_s: dict[int, float] = field(default_factory=dict)
    stats: TransferStats = field(default_factory=TransferStats)
    downlink_airtime_s: float = 0.0   # clock when dissemination finished
    downlink_busy_s: float = 0.0      # downlink frames on the air
    # constrained-device energy accounting (RadioProfile × the medium's
    # per-client tx/rx/idle-listen seconds) — docs/concurrent_uplink.md
    per_client_energy_j: dict[int, float] = field(default_factory=dict)
    duty_cycle: dict[int, float] = field(default_factory=dict)


@dataclass(frozen=True)
class RadioProfile:
    """Radio power draw (watts) for per-client energy accounting.

    Defaults approximate a CC2420-class 802.15.4 transceiver at 3 V:
    ~17.4 mA transmitting at 0 dBm, ~18.8 mA receiving, and an aggressive
    low-power-listening idle mode.  Energy for a round is

        tx_s * tx_w + rx_s * rx_w + idle_listen_s * idle_w

    where ``idle_listen`` is the client's radio-on window minus its
    tx/rx airtime — the seconds it spends listening to other clients'
    frames and gaps, which on a shared medium is where most of a
    constrained device's budget actually goes.
    """

    tx_w: float = 0.0522
    rx_w: float = 0.0564
    idle_w: float = 0.00128


class ArbitrationPolicy:
    """Pluggable contention arbitration: pick who transmits next.

    ``pick(medium, n, session_at)`` returns the winner's position in
    ``[0, n)`` among the ready contenders **in session insertion order**;
    ``session_at(i)`` lazily resolves the i-th contender's session (may
    return None on legacy call sites that only know client ids).  It is
    only consulted for ``n > 1`` — a lone contender short-circuits in
    ``SharedMedium.arbitrate`` without any RNG draw, so a lone client's
    schedule is identical at any concurrency and under every policy.
    """

    name = "base"

    def pick(self, medium: "SharedMedium", n: int, session_at) -> int:
        raise NotImplementedError


class SeededRandomArbitration(ArbitrationPolicy):
    """The default: a seeded uniform draw over the ready contenders —
    deterministic interleaving, exact replay per seed.  Exactly one RNG
    draw per contended slot, which is what pins the event-heap scheduler
    byte-identical to the legacy per-frame scan."""

    name = "seeded-random"

    def pick(self, medium: "SharedMedium", n: int, session_at) -> int:
        return int(medium._rng.integers(n))


class ShortestRemainingArbitration(ArbitrationPolicy):
    """Shortest-remaining-first: grant the contender with the fewest
    staged payload bytes left this window (``remaining_hint``), ties to
    the earliest session.  Drains nearly-done uploads first, so the
    server folds models (and frees gather buffers) as early as possible.
    No RNG draw — fully deterministic given the session set."""

    name = "shortest-remaining-first"

    def pick(self, medium: "SharedMedium", n: int, session_at) -> int:
        best, best_key = 0, None
        for i in range(n):
            key = getattr(session_at(i), "remaining_hint", 0)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best


class DeadlineAwareArbitration(ArbitrationPolicy):
    """Deadline-aware (least-slack-first): grant the contender with the
    MOST staged bytes left — the straggler closest to missing the round
    deadline.  Minimizes the worst-case completion time at the cost of
    later first-folds; ties to the earliest session.  No RNG draw."""

    name = "deadline-aware"

    def pick(self, medium: "SharedMedium", n: int, session_at) -> int:
        best, best_key = 0, None
        for i in range(n):
            key = getattr(session_at(i), "remaining_hint", 0)
            if best_key is None or key > best_key:
                best, best_key = i, key
        return best


ARBITRATION_POLICIES = {
    p.name: p for p in (SeededRandomArbitration, ShortestRemainingArbitration,
                        DeadlineAwareArbitration)
}


def resolve_arbitration(spec) -> ArbitrationPolicy:
    """An ``ArbitrationPolicy`` instance passes through; a name resolves
    against ``ARBITRATION_POLICIES``."""
    if isinstance(spec, ArbitrationPolicy):
        return spec
    try:
        return ARBITRATION_POLICIES[spec]()
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown arbitration policy {spec!r} (choose from "
            f"{sorted(ARBITRATION_POLICIES)})") from None  # sched-ok: error-message formatting, not the frame loop


class SharedMedium:
    """Deterministic (seeded) shared-medium simulation.

    All state advances through three entry points: ``arbitrate`` picks the
    next transmitter among contenders, ``transmit`` puts one tagged frame
    on the air (returns the frames *released* to the receiver, which lag
    behind transmissions when jitter reorders them), and
    ``transmit_payload`` sends one CON control payload (feedback) with
    link-layer retransmissions.  ``advance_to`` models time nobody could
    use (every contender waiting on turnaround).
    """

    def __init__(self, *, seed: int = 0, link_bps: int = LINK_BPS,
                 frame_drop_prob: float = 0.0,
                 reorder_prob: float = 0.0, max_reorder_lag: int = 8,
                 turnaround_s: float = 0.05,
                 chunk_drop: ChunkDropFn | None = None,
                 faults: object | None = None,
                 arbitration: ArbitrationPolicy | str = "seeded-random",
                 radio: RadioProfile | None = None) -> None:
        if not 0.0 <= frame_drop_prob < 1.0:
            raise ValueError("frame_drop_prob must be in [0, 1)")
        if not 0.0 <= reorder_prob <= 1.0:
            raise ValueError("reorder_prob must be in [0, 1]")
        if max_reorder_lag < 1:
            raise ValueError("max_reorder_lag must be >= 1")
        self._rng = np.random.default_rng(seed)
        self.link_bps = link_bps
        self.frame_drop_prob = frame_drop_prob
        self.reorder_prob = reorder_prob
        self.max_reorder_lag = max_reorder_lag
        self.turnaround_s = turnaround_s
        # chunk_drop(uri, window, chunk_index, client) -> drop whole chunk?
        # Replaces the frame-level RNG for *data* delivery decisions (bytes
        # are still counted), mirroring LossyLink.chunk_drop — but keyed by
        # the transmitting client, since the medium has one receiver (the
        # server) and many senders.
        self.chunk_drop = chunk_drop
        # Optional fault schedule (fl.faults.FaultPlan shape, duck-typed):
        # blackout intervals on the medium clock and per-frame
        # corrupt/truncate/drop verdicts, applied *after* the RNG draws so
        # a plan never perturbs the fault-free arbitration/loss streams.
        self.faults = faults
        self.clock = 0.0
        self.busy_s = 0.0
        self.idle_s = 0.0
        # dissemination-phase accounting for whole-round schedules
        # (run_medium_downlink stamps these; MediumReport reads them back)
        self.downlink_airtime_s = 0.0
        self.downlink_busy_s = 0.0
        self.stats = TransferStats()
        self.arbitration = resolve_arbitration(arbitration)
        self.radio = radio if radio is not None else RadioProfile()
        self.frames_sent = 0               # data frames put on the air
        self.frames_lost = 0               # ...that did not reach a receiver
        self._seq = 0                      # frames transmitted (global order)
        # Holdback entries are shared mutable cells [release_seq, seq,
        # frame, alive]: pushed onto BOTH the global release heap and the
        # transmitting client's per-client heap.  Whichever side consumes
        # an entry first (timed release vs window-boundary flush)
        # tombstones it (alive=False); the other side lazily skips the
        # corpse.  This is what makes ``flush(client)`` O(held_by_client
        # × log) instead of sort-the-world per window boundary.
        self._holdback: list = []
        self._holdback_by_client: dict[int, list] = {}
        # per-client radio-airtime accounting (seconds transmitting /
        # receiving), folded with RadioProfile into MediumReport energy
        self._tx_s: dict[int, float] = {}
        self._rx_s: dict[int, float] = {}

    # -- time ---------------------------------------------------------------

    def frame_airtime(self, wire_bytes: int) -> float:
        return (wire_bytes + LOWPAN_OVERHEAD) * 8 / self.link_bps

    def advance_to(self, t: float) -> None:
        """Advance the clock over a gap no contender could fill."""
        if t > self.clock:
            self.idle_s += t - self.clock
            self.clock = t

    # -- arbitration --------------------------------------------------------

    def arbitrate(self, contenders: Sequence[int],
                  sessions: Sequence | None = None) -> int:
        """Pick the next transmitter among contending client ids via the
        configured ``ArbitrationPolicy`` (deterministic).  One contender
        short-circuits without consulting the policy — no RNG draw — so a
        lone client's schedule is identical at any concurrency.
        ``sessions`` (same order as ``contenders``) gives state-aware
        policies their inputs; id-only call sites may omit it."""
        if len(contenders) == 1:
            return contenders[0]
        if sessions is None:
            session_at = lambda i: None          # noqa: E731
        else:
            session_at = lambda i: sessions[i]   # noqa: E731
        return contenders[self.arbitration.pick(self, len(contenders),
                                                session_at)]

    # -- data frames --------------------------------------------------------

    def transmit(self, frame: TaggedFrame, stats: TransferStats,
                 drop: bool | None = None) -> list[TaggedFrame]:
        """Put one tagged frame on the air (NON — no link-layer retry; loss
        recovery belongs to the chunk layer's NACK round-trip).

        ``drop`` forces the delivery verdict (the chunk_drop schedule);
        ``None`` draws from the frame-level RNG.  Returns the frames
        released to the receiver at this step: a delivered frame may be
        held back (jitter) and released after later frames, so the return
        value lags transmissions when reordering strikes.
        """
        a = self.frame_airtime(frame.wire_bytes)
        self.clock += a
        self.busy_s += a
        self._tx_s[frame.client] = self._tx_s.get(frame.client, 0.0) + a
        for s in (stats, self.stats):
            s.frames += 1
            s.blocks += 1
            s.wire_bytes += frame.wire_bytes
            s.link_bytes += frame.wire_bytes + LOWPAN_OVERHEAD
        if drop is None:
            drop = (self.frame_drop_prob > 0.0
                    and float(self._rng.random()) < self.frame_drop_prob)
        # fault schedule verdicts come after the RNG draw so the per-frame
        # drop stream replays identically with and without a plan (the
        # differential recovery oracle relies on it)
        if self.faults is not None:
            if self.faults.blackout_at(self.clock - a):
                drop = True          # the frame started inside a blackout
            elif not drop:
                verdict = self.faults.frame_verdict(
                    client=frame.client, window=frame.window,
                    chunk_index=frame.chunk_index,
                    block_num=frame.block_num)
                if verdict == "drop":
                    drop = True
                elif verdict is not None:
                    frame = _damage_frame(frame, verdict)
                    if frame is None:
                        drop = True  # nothing left to deliver
        self._seq += 1
        self.frames_sent += 1
        if not drop:
            lag = 0
            if self.reorder_prob and float(self._rng.random()) < self.reorder_prob:
                lag = 1 + int(self._rng.integers(self.max_reorder_lag))
            # (release_seq, seq) is unique per entry, so heap comparisons
            # never reach the frame/alive cells
            entry = [self._seq + lag, self._seq, frame, True]
            heapq.heappush(self._holdback, entry)
            heapq.heappush(
                self._holdback_by_client.setdefault(frame.client, []), entry)
        else:
            self.frames_lost += 1
        return self._release()

    def transmit_downlink(self, frame: TaggedFrame, stats: TransferStats,
                          *, receivers: Sequence[int],
                          drops: dict[int, bool] | None = None
                          ) -> dict[int, TaggedFrame | None]:
        """Put one multicast downlink frame on the air: airtime and byte
        accounting once (one wire transmission reaches the whole cohort),
        delivery decided per receiving client.

        ``receivers`` are the listening clients' ids in deterministic
        order — each gets its own loss draw (independent fading), or a
        forced verdict from ``drops`` (the chunk_drop schedule, keyed by
        the *receiving* client).  Blackouts kill the frame for everyone;
        per-client ``FrameFault`` verdicts damage individual copies.  The
        fault verdicts come after every RNG draw so a plan never perturbs
        the fault-free loss streams.  Downlink frames release in order (no
        holdback): multicast receivers slot blocks from one transmission
        sequence, so reorder jitter is an uplink-contention artifact.
        Returns ``{client: delivered frame or None}``.
        """
        a = self.frame_airtime(frame.wire_bytes)
        t0 = self.clock
        self.clock += a
        self.busy_s += a
        for cid in receivers:
            # every listener's radio is in rx for the whole frame — paying
            # for airtime it may not even decode is the multicast deal
            self._rx_s[cid] = self._rx_s.get(cid, 0.0) + a
        for s in (stats, self.stats):
            s.frames += 1
            s.blocks += 1
            s.wire_bytes += frame.wire_bytes
            s.link_bytes += frame.wire_bytes + LOWPAN_OVERHEAD
        self._seq += 1
        self.frames_sent += 1
        blackout = (self.faults is not None
                    and self.faults.blackout_at(t0))
        out: dict[int, TaggedFrame | None] = {}
        for cid in receivers:
            drop = drops.get(cid) if drops is not None else None
            if drop is None:
                drop = (self.frame_drop_prob > 0.0
                        and float(self._rng.random()) < self.frame_drop_prob)
            delivered: TaggedFrame | None = frame
            if blackout:
                drop = True
            elif not drop and self.faults is not None:
                verdict = self.faults.frame_verdict(
                    client=cid, window=frame.window,
                    chunk_index=frame.chunk_index,
                    block_num=frame.block_num)
                if verdict == "drop":
                    drop = True
                elif verdict is not None:
                    delivered = _damage_frame(frame, verdict)
                    if delivered is None:
                        drop = True
            out[cid] = None if drop else delivered
        if receivers and all(v is None for v in out.values()):
            self.frames_lost += 1    # loss_estimate: nobody heard it
        return out

    def loss_estimate(self) -> float:
        """Observed frame-loss fraction so far — what medium-aware backoff
        scales its delays by (a congested/black channel backs off harder)."""
        if not self.frames_sent:
            return 0.0
        return self.frames_lost / self.frames_sent

    def _release(self) -> list[TaggedFrame]:
        out = []
        while self._holdback and self._holdback[0][0] <= self._seq:
            entry = heapq.heappop(self._holdback)
            if entry[3]:
                entry[3] = False     # tombstone for the per-client heap
                out.append(entry[2])
        return out

    def flush(self, client: int | None = None) -> list[TaggedFrame]:
        """Release held-back frames immediately — all of them, or one
        client's (a window boundary: its feedback logically follows every
        frame of the window, so any of its frames still 'in flight' have
        arrived by then).

        Heap pops yield ascending (release_seq, seq) — the same order the
        timed ``_release`` would have used — without ever sorting the
        whole holdback list: one client's flush costs O(held_by_client ×
        log), not O(total_held × log) per window boundary.
        """
        if client is None:
            out = []
            while self._holdback:
                entry = heapq.heappop(self._holdback)
                if entry[3]:
                    entry[3] = False
                    out.append(entry[2])
            self._holdback_by_client.clear()
            return out
        heap = self._holdback_by_client.get(client)
        if not heap:
            return []
        out = []
        while heap:
            entry = heapq.heappop(heap)
            if entry[3]:
                entry[3] = False     # tombstone for the global heap
                out.append(entry[2])
        return out

    # -- control payloads ---------------------------------------------------

    def transmit_payload(self, payload, *, uri: str,
                         code: Code = Code.CONTENT,
                         stats: TransferStats | None = None,
                         ring=None, tx_client: int | None = None,
                         rx_client: int | None = None
                         ) -> tuple[bool, TransferStats]:
        """One CON control transfer (NACK/ACK feedback) on the medium.

        Per-frame ack + retransmission up to MAX_RETRANSMIT, every attempt
        advancing the clock — control traffic competes for the same
        airtime as data.  ``ring`` (a ``BlockReceiveRing``) collects the
        delivered blocks when the caller needs the reassembled payload
        (monolithic dissemination on the medium).  ``tx_client`` /
        ``rx_client`` attribute the airtime to a client's radio (energy
        accounting) when the client is the sender (uplink NACK) or the
        listener (server feedback).  Returns ``(delivered, stats)``; an
        undelivered feedback message costs the sender a window (it polls
        again), never correctness.
        """
        def on_frame(wire: int) -> None:
            a = self.frame_airtime(wire)
            self.clock += a
            self.busy_s += a
            if tx_client is not None:
                self._tx_s[tx_client] = self._tx_s.get(tx_client, 0.0) + a
            if rx_client is not None:
                self._rx_s[rx_client] = self._rx_s.get(rx_client, 0.0) + a

        def drop() -> bool:
            lost = (self.frame_drop_prob > 0.0
                    and float(self._rng.random()) < self.frame_drop_prob)
            if self.faults is not None and self.faults.blackout_at(self.clock):
                return True      # RNG drawn first: stream stays aligned
            return lost

        out = con_blockwise_transfer(
            payload, uri=uri, code=code, drop=drop, on_frame=on_frame,
            ring=ring)
        self.stats.add(out)
        if stats is not None:
            stats.add(out)
        return not out.failed_messages, out

    # -- energy -------------------------------------------------------------

    def energy_report(self, windows: dict[int, tuple[float, float]]
                      ) -> tuple[dict[int, float], dict[int, float]]:
        """Fold per-client tx/rx airtime with ``RadioProfile`` into energy
        (joules) and duty cycle per client.

        ``windows`` maps client -> (radio_on_start, radio_on_end) on the
        medium clock; idle-listen is the window minus the client's own
        tx/rx seconds (listening to other clients' frames and gaps).
        Duty cycle is the active (tx+rx) fraction of the window.
        """
        energy: dict[int, float] = {}
        duty: dict[int, float] = {}
        for cid, (t0, t1) in windows.items():
            tx = self._tx_s.get(cid, 0.0)
            rx = self._rx_s.get(cid, 0.0)
            span = max(0.0, t1 - t0)
            idle = max(0.0, span - tx - rx)
            energy[cid] = (tx * self.radio.tx_w + rx * self.radio.rx_w
                           + idle * self.radio.idle_w)
            duty[cid] = min(1.0, (tx + rx) / span) if span > 0.0 else 0.0
        return energy, duty
