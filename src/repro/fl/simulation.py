"""End-to-end FL simulation: server + clients over the simulated CoAP link.

Drives the paper's full communication diagram (Fig. 2) with exact
byte/frame accounting per message type, CDDL validation of every message on
the wire, deterministic fault injection (fl.faults), and round
checkpointing.  The *round lifecycle* — deadlines on the virtual clock,
quorum at the deadline, medium-aware backoff, crash-recoverable
aggregation — lives in ``fl.round.RoundEngine``; this class is the driver
that owns the clients, the link, and the byte accounting.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.core import cddl, fastpath
from repro.core.messages import (
    CHUNK_ENCODINGS,
    FLGlobalModelUpdate,
    ParamsEncoding,
)
from repro.core.params_codec import Q8_BLOCK, quantize_q8
from repro.fl.chunking import (
    ChunkTransferReport,
    run_medium_downlink,
    run_selective_repeat,
)
from repro.fl.client import FLClient
from repro.fl.faults import FaultPlan
from repro.fl.round import RoundEngine, RoundPolicy
from repro.fl.server import FLServer, OrchestrationConfig, RoundResult
from repro.transport.coap import BlockReceiveRing, Code, TransferStats
from repro.transport.medium import MediumReport
from repro.transport.network import LossyLink, as_wire_payload


@dataclass
class MessageAccounting:
    by_type: dict[str, TransferStats] = field(default_factory=dict)

    def record(self, mtype: str, stats: TransferStats) -> None:
        agg = self.by_type.setdefault(mtype, TransferStats())
        agg.add(stats)

    def summary(self) -> dict:
        return {k: vars(v) for k, v in self.by_type.items()}


@dataclass
class SimulationReport:
    rounds: list[RoundResult]
    accounting: MessageAccounting
    final_val_loss: float
    final_train_loss: float


class FLSimulation:
    def __init__(self, server: FLServer, clients: list[FLClient],
                 drop_prob: float = 0.0, seed: int = 0,
                 multicast_global: bool = True,
                 chunk_elems: int | None = None,
                 uplink_mode: str = "sequential",
                 uplink_reorder_prob: float = 0.0,
                 uplink_turnaround_s: float = 0.05,
                 faults: FaultPlan | None = None,
                 round_policy: RoundPolicy | None = None,
                 chunk_encoding: ParamsEncoding | str =
                 ParamsEncoding.TA_F32,
                 residual_uplink: bool = False,
                 downlink_mode: str = "link",
                 arbitration="seeded-random",
                 radio=None,
                 legacy_scheduler: bool = False) -> None:
        self.server = server
        self.clients = {c.client_id: c for c in clients}
        # arbitration: SharedMedium contention policy (name or
        # ArbitrationPolicy) — seeded-random (default), shortest-
        # remaining-first, deadline-aware; radio: RadioProfile for
        # per-client energy accounting; legacy_scheduler: run uplinks on
        # the original per-frame scan instead of the event heap (the
        # differential oracle — byte-identical under the default policy)
        self.arbitration = arbitration
        self.radio = radio
        self.legacy_scheduler = legacy_scheduler
        # faults: one seeded, replayable schedule of client/server crashes,
        # blackouts, frame damage, feedback loss, and chunk loss
        # (fl.faults.FaultPlan) threaded through every transport layer;
        # round_policy: deadline / training-time / backoff / snapshot
        # policy the RoundEngine drives the round with (fl.round).
        self.faults = faults
        self.round_policy = round_policy
        self.link = LossyLink(drop_prob=drop_prob, seed=seed, faults=faults)
        if faults is not None and faults.as_chunk_drop() is not None:
            # the plan's seeded chunk-loss schedule replaces the ad-hoc
            # link.chunk_drop hook (both directions, both uplink modes)
            self.link.chunk_drop = faults.as_chunk_drop()
        self.accounting = MessageAccounting()
        self.multicast_global = multicast_global
        # chunk_elems: when set, model transfers in BOTH directions run as
        # selective-repeat FL_Model_Chunk streams of this many parameters
        # each (docs/chunk_protocol.md) instead of monolithic updates.
        # chunk_encoding picks the chunk wire format (f32 / f16 /
        # q8-block; the payload's CBOR tag is the per-chunk discriminator
        # and the CRC covers the encoded bytes), so cfg.params_encoding
        # then only governs the tiny progress updates; the downlink
        # stream is inherently multicast (one transfer reaches all
        # receivers), so multicast_global does not apply to it either.
        # residual_uplink: clients transmit local − last_global and the
        # server folds the deltas against its copy of that reference.
        self.chunk_elems = chunk_elems
        if isinstance(chunk_encoding, str):
            chunk_encoding = ParamsEncoding(chunk_encoding)
        if chunk_encoding not in CHUNK_ENCODINGS:
            raise ValueError(
                f"{chunk_encoding.value} is not a chunk encoding (choose "
                f"from {[e.value for e in CHUNK_ENCODINGS]})")
        if chunk_elems is None and (
                chunk_encoding is not ParamsEncoding.TA_F32
                or residual_uplink):
            raise ValueError("chunk_encoding / residual_uplink require "
                             "chunked transfers (set chunk_elems)")
        if (chunk_encoding is ParamsEncoding.Q8 and chunk_elems is not None
                and chunk_elems % Q8_BLOCK):
            raise ValueError(
                f"q8 chunk streams need chunk_elems to be a multiple of "
                f"{Q8_BLOCK} (got {chunk_elems})")
        self.chunk_encoding = chunk_encoding
        self.residual_uplink = bool(residual_uplink)
        # the server's copy of the reference the cohort installed this
        # round (what residual folds resolve against); set per
        # dissemination — under a lossy chunk encoding it is the
        # dequantized model, not the exact f32 global
        self._residual_ref: np.ndarray | None = None
        # uplink_mode: "sequential" uploads chunked local models client by
        # client over the CON unicast link (the legacy shape);
        # "interleaved" schedules every reporter's selective-repeat windows
        # concurrently over one SharedMedium contention domain
        # (docs/concurrent_uplink.md) — frames arbitrate per-slot, blocks
        # may reorder, and the server aggregates incrementally as each
        # client's reassembly completes.
        if uplink_mode not in ("sequential", "interleaved"):
            raise ValueError(f"unknown uplink_mode {uplink_mode!r}")
        self.uplink_mode = uplink_mode
        # downlink_mode: "link" disseminates over the point-to-point
        # LossyLink (legacy); "medium" routes dissemination AND its
        # NACK/ACK feedback through the round's SharedMedium, so one
        # FaultPlan — blackouts, frame faults, feedback loss — governs
        # the whole round on one virtual clock and MediumReport carves
        # out the dissemination airtime (docs/fault_model.md).
        if downlink_mode not in ("link", "medium"):
            raise ValueError(f"unknown downlink_mode {downlink_mode!r}")
        self.downlink_mode = downlink_mode
        # the whole-round contention domain, created per round by the
        # RoundEngine when downlink_mode == "medium"
        self._round_medium = None
        # per-dissemination churn bookkeeping (who died downloading, who
        # came back) — the engine reads these for fault attribution
        self._downlink_crashed: set[int] = set()
        self._downlink_resumed: set[int] = set()
        self.uplink_reorder_prob = uplink_reorder_prob
        self.uplink_turnaround_s = uplink_turnaround_s
        self.last_downlink_report: ChunkTransferReport | None = None
        self.last_uplink_report: ChunkTransferReport | None = None
        self.last_uplink_reports: list[ChunkTransferReport] = []
        self.last_medium_report: MediumReport | None = None
        self._seed = seed
        self._rng = np.random.default_rng(seed)

    # -- wire helpers (validate every message against its CDDL schema) -------

    def _send(self, payload, mtype: str, uri: str, code: Code, *,
              validated: bool = False):
        """Validate against CDDL, push over the lossy link, deliver.

        ``payload`` is contiguous bytes or a vectored segment list /
        ``ScatterPayload`` from ``to_cbor_segments`` — validation decodes
        the segments in place (no join), the link counts and frames them
        without joining, and delivery comes back as a ``BlockReceiveRing``
        whose arena is the receiver's *single* owned copy of the wire
        bytes; ``from_cbor_segments`` decodes it as borrowed views, so no
        second (join) copy is ever layered on top.  Multi-send loops
        (unicast dissemination) pass ``validated=True`` so the validation
        decode happens once per message, not once per send.
        Returns the ring, or None if the transfer failed after max
        retransmissions (treated upstream as a dropout — the FL round
        continues without this message)."""
        payload = as_wire_payload(payload)
        if not validated:
            cddl.validate(fastpath.decode(payload), cddl.SCHEMAS[mtype])
        stats, ring = self.link.deliver_payload(payload, uri=uri, code=code)
        self.accounting.record(mtype, stats)
        return ring

    def _disseminate_chunked(self, receivers: list[int]) -> list[int]:
        """Stream the global model as FL_Model_Chunk messages with
        selective-repeat recovery (docs/chunk_protocol.md).

        NON multicast: one wire stream reaches every receiver, each of which
        loses chunks independently.  After every window the clients NACK
        their missing chunk indices (or ACK completion) and the server
        re-multicasts only the union of the missing sets.  A client still
        incomplete when the window budget runs out is a dropout for the
        round — everyone else trains.  Returns the clients that installed
        the full model.
        """
        if not receivers:
            return []
        with obs.span(obs.SERVER_ENCODE):
            chunks = list(self.server.global_update_chunks(
                self.chunk_elems, encoding=self.chunk_encoding))
            if self.residual_uplink:
                # record the server's copy of the reference the cohort is
                # about to install: under a lossy chunk encoding the
                # clients hold the *dequantized* model, and residual folds
                # must resolve against exactly that vector, not the f32
                # global
                flat = self.server.global_params
                if self.chunk_encoding is ParamsEncoding.TA_F16:
                    self._residual_ref = flat.astype("<f2").astype("<f4")
                elif self.chunk_encoding is ParamsEncoding.Q8:
                    self._residual_ref = quantize_q8(
                        flat, Q8_BLOCK)[2].astype("<f4", copy=False)
                else:
                    self._residual_ref = flat
        self._downlink_crashed = set()
        self._downlink_resumed = set()
        if self.downlink_mode == "medium" and self._round_medium is not None:
            medium = self._round_medium
            with obs.span(obs.SCHED_DOWNLINK):
                report = run_medium_downlink(
                    medium, chunks, [self.clients[cid] for cid in receivers],
                    uri="fl/model/chunk", feedback_uri="fl/model/chunk/fb",
                    record=self.accounting.record,
                    backoff=(self.round_policy.backoff
                             if self.round_policy else None),
                    client_ids=receivers, faults=self.faults,
                    checkpoint=self._client_checkpoint,
                    on_crash=self._client_crash_cb,
                    resume_client=self.restart_client)
            self.last_downlink_report = report
            self._publish_downlink_report(medium)
            # the rest of the round continues on the same clock axis
            self.link.advance_to_round(medium.clock)
            return [receivers[i] for i in report.completed]
        with obs.span(obs.SCHED_DOWNLINK):
            report = run_selective_repeat(
                self.link, chunks, [self.clients[cid] for cid in receivers],
                uri="fl/model/chunk", feedback_uri="fl/model/chunk/fb",
                multicast=True, record=self.accounting.record,
                client_ids=receivers)
        self.last_downlink_report = report
        return [receivers[i] for i in report.completed]

    def _collect_chunked(self, cid: int, *, backoff=None,
                         faults: FaultPlan | None = None,
                         airtime_budget_s: float | None = None,
                         encoding: ParamsEncoding | str | None = None,
                         residual: bool | None = None,
                         keep_partial: bool = False,
                         poll_first: bool = False,
                         resumed: bool = False
                         ) -> np.ndarray | None:
        """Chunked client → server local-model upload (reverse direction).

        CON unicast chunk stream into the server's per-client reassembly
        endpoint; the *server* NACKs missing indices and the client re-sends
        only those.  ``backoff`` delays repair windows, ``airtime_budget_s``
        bounds the transfer's share of the round deadline, and ``faults``
        injects this client's crash point / feedback losses (fl.round
        threads the round policy through here).  Returns the reassembled
        flat f32 params, or None if the upload never completed (treated
        upstream as a dropout or straggler).  ``encoding``/``residual``
        override the simulation defaults (the round engine passes the
        values its aggregation snapshot recorded, so a resumed round
        re-collects in the encoding the crashed round was using).

        Crash-resume hooks: ``keep_partial`` leaves the server's partial
        reassembly endpoint in place when the upload dies mid-transfer
        (so a resumed client can finish it), ``poll_first`` makes window
        0 a pure feedback poll (retransmit only what the server NACKs),
        and ``resumed`` suppresses the fault plan's crash injection —
        a client does not crash twice at the same coordinate."""
        chunks = self.clients[cid].local_model_chunks(
            self.chunk_elems,
            encoding=(self.chunk_encoding if encoding is None else encoding),
            residual=(self.residual_uplink if residual is None else residual))
        sender_crash = None
        feedback_lost = None
        if faults is not None:
            crash = faults.client_crash(cid)
            if (not resumed and crash is not None
                    and crash.phase in ("upload", "repair")):
                sender_crash = (crash.crash_window, crash.at_chunk)
            if faults.feedback_losses:
                feedback_lost = (lambda ridx, w:
                                 faults.feedback_lost(cid, w))
        report = run_selective_repeat(
            self.link, chunks, [self.server.uplink_endpoint(cid)],
            uri="fl/model/upload", feedback_uri="fl/model/upload/fb",
            multicast=False, record=self._record_uplink,
            backoff=backoff, turnaround_s=self.uplink_turnaround_s,
            airtime_budget_s=airtime_budget_s,
            sender_crash=sender_crash, feedback_lost=feedback_lost,
            client_ids=[cid], poll_first=poll_first)
        self.last_uplink_report = report
        return self.server.pop_uplink(cid, keep_partial=keep_partial)

    def _record_uplink(self, mtype: str, stats: TransferStats) -> None:
        # chunk traffic is accounted per direction; control messages share
        # their message-type buckets with the downlink.
        self.accounting.record(
            "FL_Model_Chunk_Uplink" if mtype == "FL_Model_Chunk" else mtype,
            stats)

    # -- dissemination (phase 1 of the round; the engine calls this) ----------

    def _disseminate(self, selected: list[int]
                     ) -> tuple[list[int], list[int]]:
        """Global model dissemination: multicast = one wire transfer
        reaching all clients (§VI-B2); unicast = one per client;
        chunk_elems switches to the streaming FL_Model_Chunk path.
        Returns ``(receivers, dropped)`` — clients holding the new model,
        and clients the round continues *without*.

        Degradation semantics: a failed *unicast* send drops exactly that
        client (everyone else trains); a failed *multicast* transfer keeps
        all-or-nothing semantics — one wire stream either reached the
        cohort or it did not."""
        if self.chunk_elems is not None:
            receivers = self._disseminate_chunked(selected)
            return receivers, [c for c in selected if c not in receivers]
        server = self.server
        msg = server.global_update_message()
        # vectored wire form: the params payload crosses the link as a
        # borrowed view of the live global vector (zero encode copies);
        # validated once over the segments, however many sends follow
        payload = fastpath.ScatterPayload(
            msg.to_cbor_segments(server.cfg.params_encoding))
        cddl.validate(fastpath.decode(payload),
                      cddl.SCHEMAS["FL_Global_Model_Update"])
        medium = (self._round_medium
                  if self.downlink_mode == "medium" else None)
        if self.multicast_global:
            if medium is not None:
                # monolithic dissemination on the shared medium: one CON
                # transfer on the round clock, decoded from its ring
                busy0 = medium.busy_s
                ring = BlockReceiveRing()
                with obs.span(obs.SCHED_DOWNLINK):
                    ok, stats = medium.transmit_payload(
                        payload, uri="fl/model", code=Code.POST, ring=ring)
                self.accounting.record("FL_Global_Model_Update", stats)
                medium.downlink_airtime_s = medium.clock
                medium.downlink_busy_s = medium.busy_s - busy0
                self._publish_downlink_report(medium)
                self.link.advance_to_round(medium.clock)
                if not ok:
                    return [], list(selected)
                self._install(selected, ring)
                return list(selected), []
            # one wire transfer reaches everyone; every client decodes
            # the same delivered ring (its arena is the receiver-side
            # owned copy, decoded as views)
            with obs.span(obs.SCHED_DOWNLINK):
                ring = self._send(payload, "FL_Global_Model_Update",
                                  "fl/model", Code.POST, validated=True)
            if ring is None:
                return [], list(selected)
            self._install(selected, ring)
            return list(selected), []
        # unicast: deliver + decode per client so only ONE ring is alive
        # at a time (N simultaneous arenas would put peak memory back at
        # N× model); a failed send drops only its client
        receivers, dropped = [], []
        busy0 = medium.busy_s if medium is not None else 0.0
        for cid in selected:
            with obs.span(obs.SCHED_DOWNLINK):
                if medium is not None:
                    ring = BlockReceiveRing()
                    ok, stats = medium.transmit_payload(
                        payload, uri="fl/model", code=Code.POST, ring=ring)
                    self.accounting.record("FL_Global_Model_Update", stats)
                    if not ok:
                        ring = None
                else:
                    ring = self._send(payload, "FL_Global_Model_Update",
                                      "fl/model", Code.POST, validated=True)
            if ring is None:
                dropped.append(cid)
                continue
            self._install([cid], ring)
            receivers.append(cid)
        if medium is not None:
            medium.downlink_airtime_s = medium.clock
            medium.downlink_busy_s = medium.busy_s - busy0
            self._publish_downlink_report(medium)
            self.link.advance_to_round(medium.clock)
        return receivers, dropped

    def _install(self, cids: list[int], ring) -> None:
        """Each of ``cids`` decodes the delivered monolithic global from
        ``ring`` and installs it."""
        with obs.span(obs.ASSEMBLE):
            for cid in cids:
                self.clients[cid].handle_global_model(
                    FLGlobalModelUpdate.from_cbor_segments(ring))

    def _publish_downlink_report(self, medium) -> None:
        """Downlink-only medium accounting, published right after the
        dissemination so a sequential (off-medium) uplink still reports
        the dissemination airtime; an interleaved uplink overwrites this
        with the whole-round report on the same medium."""
        self.last_medium_report = MediumReport(
            airtime_s=medium.clock, busy_s=medium.busy_s,
            idle_s=medium.idle_s, stats=medium.stats,
            downlink_airtime_s=medium.downlink_airtime_s,
            downlink_busy_s=medium.downlink_busy_s)

    # -- client lifecycle hooks (crash-resume + churn; fl.round drives) -------

    def _client_checkpoint(self, cid: int) -> None:
        """Persist one client's durable state (no-op for clients without
        a ``checkpoint_dir``)."""
        self.clients[cid].save_client_state()

    def _client_crash_cb(self, cid: int) -> None:
        """A download-phase ``ClientCrash`` fired: wipe the client's
        volatile state (the medium downlink driver's ``on_crash``)."""
        self._downlink_crashed.add(cid)
        self.clients[cid].simulate_crash()

    def restart_client(self, cid: int) -> bool:
        """Reboot one client: volatile state is lost, then the durable
        checkpoint — if any — is restored.  Returns True when the client
        came back with state (the crash is *resumable*); False degrades
        to the legacy dropout."""
        client = self.clients[cid]
        client.simulate_crash()
        ok = client.try_restore_client()
        if ok and cid in self._downlink_crashed:
            self._downlink_resumed.add(cid)
        return ok

    def _push_stale_upload(self, cid: int) -> None:
        """A rejoining client replays the upload of the round it left in —
        every chunk arrives carrying the *previous* generation's
        (model_id, round) and is rejected idempotently at the
        ``UplinkEndpoint`` generation gate.  Models an out-of-band
        arrival (the engine calls this before the round opens): no wire
        accounting, no reassembly state touched.  Raw f32 chunks on
        purpose — a lossy replay would mutate the client's error-feedback
        state, and a rejected upload must leave no trace anywhere."""
        client = self.clients.get(cid)
        if client is None or client.params is None:
            return
        server = self.server
        if (client.round >= server.round
                and client.model_id == server.model_id):
            return      # not actually stale: nothing to replay
        if self.chunk_elems is None:
            return      # monolithic stale uploads are culled in aggregate()
        ep = server.uplink_endpoint(cid)
        for msg in client.local_model_chunks(
                self.chunk_elems, encoding=ParamsEncoding.TA_F32,
                residual=False):
            ep.receive_chunk(msg)       # all rejected: stale generation

    # -- one FL round (paper Fig. 2; lifecycle in fl.round) -------------------

    def run_round(self) -> RoundResult:
        """Run one round through the RoundEngine state machine: deadline
        on the virtual clock, quorum at the deadline, incremental
        aggregation with per-fold recovery snapshots."""
        return RoundEngine(self).run()

    def resume_round(self) -> RoundResult | None:
        """Finish the current round from its aggregation snapshot after a
        server restart (``FLServer.try_restore`` first): re-collects only
        the clients the completion bitmap marks unfinished and produces
        the same final model the uninterrupted round would have.  None
        when there is no snapshot — the caller runs a fresh round."""
        return RoundEngine(self).resume()

    def run(self) -> SimulationReport:
        while not self.server.done:
            self.run_round()
        last = self.server.history[-1] if self.server.history else None
        return SimulationReport(
            rounds=self.server.history,
            accounting=self.accounting,
            final_val_loss=last.mean_val_loss if last else float("nan"),
            final_train_loss=last.mean_train_loss if last else float("nan"),
        )
