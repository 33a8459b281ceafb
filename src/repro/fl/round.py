"""Round lifecycle: deadline/quorum policy, backoff, crash-recoverable
aggregation — the state machine extracted from ``FLSimulation.run_round``.

``FLSimulation`` is now only the *driver*: it owns the clients, the link,
the medium parameters and the byte accounting.  Everything that decides
*when a round gives up on whom* lives here:

  * **deadline on the virtual clock** — a round has ``deadline_s`` of
    virtual time (dissemination + training + uploads all stamped on one
    clock).  Quorum is evaluated *at the deadline*: stragglers are
    whatever has not finished by then — there is no static
    ``straggler_factor`` cull anymore; a slow client is late because its
    training/upload timeline says so;
  * **medium-aware backoff** — selective-repeat repair windows wait an
    exponentially growing, loss-scaled delay (``BackoffPolicy``) with a
    retry budget, instead of hammering the channel every
    ``MAX_REPAIR_WINDOWS`` times;
  * **graceful partial-cohort degradation** — a failed unicast send, a
    crashed client, a blackout-starved upload each drop exactly one
    participant; the round aggregates who remains, and if quorum is
    missed at the deadline the global model is left untouched (the round
    records the degradation instead of propagating a half-cohort
    average);
  * **crash-recoverable aggregation** — after every fold the
    ``RunningFedAvg`` state (TwoSum hi/lo arrays + exact weight) and the
    per-client completion bitmap are snapshotted through
    ``checkpoint/cbor_checkpoint.py``.  A server restarted mid-round
    resumes from the snapshot, re-collects *only* unfinished clients,
    ignores duplicate re-folds idempotently, and produces a final global
    model bit-identical to the uninterrupted run — the accumulator's
    order-independence (f64 TwoSum state round-trips exactly through the
    CBOR typed-array codec) is the oracle.

See docs/fault_model.md for the full fault taxonomy and the recovery
invariants the chaos CI job replays.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro import obs
from repro.fl.aggregation import RunningFedAvg
from repro.fl.chunking import MAX_REPAIR_WINDOWS
from repro.fl.faults import FaultPlan
from repro.fl.server import RoundResult
from repro.transport.coap import Code
from repro.transport.medium import SharedMedium


@dataclass(frozen=True)
class BackoffPolicy:
    """Exponential, medium-aware backoff for selective-repeat repair
    windows, with a retry budget.

    The delay before repair window ``attempt`` (1-based) is

        min(max_s, base * factor**(attempt-1) * (1 + loss_estimate))

    where ``base`` is ``initial_s`` (or the medium's physical turnaround
    when ``initial_s`` is None) and ``loss_estimate`` is the medium's
    observed frame-loss fraction — a lossy/congested channel backs off
    *harder*, because immediate re-transmission into the same conditions
    just burns the budget.  ``retry_budget`` bounds total repair windows
    (the role the bare ``MAX_REPAIR_WINDOWS`` constant used to play).
    """

    initial_s: float | None = None
    factor: float = 2.0
    max_s: float = 10.0
    retry_budget: int = MAX_REPAIR_WINDOWS
    medium_aware: bool = True

    def __post_init__(self) -> None:
        if self.factor < 1.0:
            raise ValueError("backoff factor must be >= 1")
        if self.retry_budget < 0:
            raise ValueError("retry budget must be >= 0")

    @property
    def max_windows(self) -> int:
        """Window budget: the initial full window plus the repairs."""
        return 1 + self.retry_budget

    def delay(self, attempt: int, *, turnaround_s: float = 0.0,
              loss_estimate: float = 0.0) -> float:
        base = self.initial_s if self.initial_s is not None else turnaround_s
        d = base * (self.factor ** max(0, attempt - 1))
        if self.medium_aware:
            d *= 1.0 + max(0.0, min(1.0, loss_estimate))
        return min(d, self.max_s)


@dataclass(frozen=True)
class RoundPolicy:
    """Per-round lifecycle policy (what used to be scattered through
    ``run_round`` as ad-hoc constants).

    * ``deadline_s`` — virtual-clock budget for the whole round; None
      disables deadline culling (quorum then follows the legacy
      aggregate-what-arrived semantics).
    * ``train_time_s`` — virtual seconds a ``straggler_factor == 1.0``
      client spends training; a client's readiness is
      ``dissemination_end + train_time_s * straggler_factor`` plus its
      progress-report airtime.
    * ``backoff`` — repair-window backoff; None keeps the legacy
      immediate-repair behaviour (window budget ``MAX_REPAIR_WINDOWS``).
    * ``snapshot_aggregation`` — write the per-fold aggregation snapshot
      (requires the server to have a checkpoint directory).
    """

    deadline_s: float | None = None
    train_time_s: float = 1.0
    backoff: BackoffPolicy | None = None
    snapshot_aggregation: bool = True


# -- crash-recoverable aggregation snapshots ---------------------------------
#
# One snapshot file per in-flight round, rewritten after every fold (the
# paper's CBOR serialization as the fault-tolerance substrate): TwoSum
# hi/lo f64 arrays round-trip exactly through the typed-array codec, the
# exact weight and fold count travel in the header meta, and client sets
# travel as fixed-width bitmaps.  ``finalize``d rounds keep the file with
# a marker until ``finish_round`` clears it, so a crash in the
# finalize->checkpoint window cannot double-apply the aggregate.

def _bitmap(ids, n: int) -> np.ndarray:
    arr = np.zeros(n, np.int32)
    idx = [i for i in set(ids) if 0 <= i < n]
    if idx:
        arr[idx] = 1
    return arr


def _ids(bitmap: np.ndarray) -> list[int]:
    return np.flatnonzero(np.asarray(bitmap)).tolist()


def _snapshot_name(round_: int) -> str:
    return f"agg_{round_:08d}"


def save_agg_snapshot(server, ctx: dict, *, finalized: bool = False) -> int:
    """Persist the in-flight aggregation state; returns bytes written.

    ``ctx`` is the round context the engine accumulated (selected /
    reporters / dropped / stopped / progress means) — everything a
    restarted server needs to finish the round without re-running
    dissemination or training.
    """
    agg = server._agg
    if agg is None:
        raise RuntimeError("no aggregation in flight to snapshot")
    n = server.cfg.num_clients
    state = agg.state()
    residual = bool(ctx.get("residual", False))
    tree = {
        "hi": state["hi"], "lo": state["lo"],
        "folded": _bitmap(server.agg_clients, n),
        "selected": _bitmap(ctx["selected"], n),
        "reporters": _bitmap(ctx["reporters"], n),
        "dropped": _bitmap(ctx["dropped"], n),
        "stopped": _bitmap(ctx["stopped"], n),
    }
    if residual:
        # the residual-uplink reference is part of the aggregation state:
        # a resumed round must finalize base + avg(deltas) against the
        # *same* base the crashed process held, to the bit
        if server._agg_base is None:
            raise RuntimeError("residual round has no aggregation base")
        tree["base"] = server._agg_base
    meta = {
        "model_id": str(server.model_id),
        "weight": float(state["weight"]),
        "n_updates": int(state["n_updates"]),
        "finalized": bool(finalized),
        "mean_train_loss": float(ctx["mean_train_loss"]),
        "mean_val_loss": float(ctx["mean_val_loss"]),
        # the chunk wire encoding and uplink mode this round runs with:
        # a restarted server re-collects unfinished clients in the same
        # encoding and knows whether a "base" leaf precedes hi/lo
        "residual": residual,
        # dataset sizes of folded clients are already inside the weight;
        # unfinished clients' sizes are re-read from their uploads
    }
    if ctx.get("chunk_encoding"):
        meta["chunk_encoding"] = str(ctx["chunk_encoding"])
    path = server.ckpt.save_named(_snapshot_name(server.round), tree,
                                  step=server.round, round_=server.round,
                                  meta=meta)
    return path.stat().st_size


def load_agg_snapshot(server) -> dict | None:
    """Restore the in-flight aggregation of the server's current round.

    Installs the accumulator + folded set into the server and returns the
    round context, or None when there is no (readable, matching) snapshot.
    """
    if server.ckpt is None:
        return None
    # peek the header first: the snapshot's leaf layout depends on what
    # was saved (a residual round carries a "base" leaf), and the leaf
    # streams are matched to ``tree_like`` positionally — guessing wrong
    # would misread every array.  Legacy snapshots carry no "residual"
    # key and default to the old layout.
    header = server.ckpt.peek_named(_snapshot_name(server.round))
    if header is None:
        return None
    residual = bool(header.get("meta", {}).get("residual", False))
    n = server.cfg.num_clients
    elems = server.global_params.size
    tree_like = {
        "hi": np.zeros(elems, np.float64), "lo": np.zeros(elems, np.float64),
        "folded": np.zeros(n, np.int32), "selected": np.zeros(n, np.int32),
        "reporters": np.zeros(n, np.int32), "dropped": np.zeros(n, np.int32),
        "stopped": np.zeros(n, np.int32),
    }
    if residual:
        tree_like["base"] = np.zeros(elems, np.float32)
    restored = server.ckpt.restore_named(_snapshot_name(server.round),
                                         tree_like)
    if restored is None:
        return None
    tree, header = restored
    meta = header.get("meta", {})
    if meta.get("model_id") != str(server.model_id):
        return None               # snapshot of some other model generation
    agg = RunningFedAvg.from_state(
        hi=tree["hi"], lo=tree["lo"],
        weight=meta["weight"], n_updates=meta["n_updates"])
    folded = _ids(tree["folded"])
    server.restore_aggregation(agg, folded,
                               finalized=bool(meta.get("finalized", False)),
                               residual_base=tree.get("base"))
    return {
        "selected": _ids(tree["selected"]),
        "reporters": _ids(tree["reporters"]),
        "dropped": _ids(tree["dropped"]),
        "stopped": _ids(tree["stopped"]),
        "folded": folded,
        "mean_train_loss": float(meta["mean_train_loss"]),
        "mean_val_loss": float(meta["mean_val_loss"]),
        "finalized": bool(meta.get("finalized", False)),
        "chunk_encoding": meta.get("chunk_encoding"),
        "residual": residual,
    }


def clear_agg_snapshot(server) -> None:
    if server.ckpt is not None:
        server.ckpt.delete_named(_snapshot_name(server.round))


# -- the round state machine --------------------------------------------------


class RoundEngine:
    """Drives one FL round through its phases on a virtual clock.

    Phase order (paper Fig. 2): dissemination -> local training +
    progress -> upload collection (+ incremental aggregation with
    per-fold snapshots) -> finalize -> finish.  ``run()`` starts a fresh
    round; ``resume()`` continues the current round from its aggregation
    snapshot after a server restart — re-collecting only the clients the
    completion bitmap says are unfinished.
    """

    def __init__(self, sim) -> None:
        self.sim = sim
        self.policy: RoundPolicy = sim.round_policy or RoundPolicy()
        self.faults: FaultPlan = sim.faults or FaultPlan()
        self.folded: list[int] = []       # fold order this process observed
        self.stragglers: list[int] = []
        self.snapshot_bytes = 0
        self.duplicate_folds = 0
        self.ctx: dict = {}
        # per-client fault attribution: first cause wins (a client that
        # crash-resumed and THEN missed the deadline is a straggler whose
        # story started with the crash) — RoundResult.fault_attribution
        self.attribution: dict[int, str] = {}

    def _attr(self, cid: int, reason: str) -> None:
        self.attribution.setdefault(cid, reason)

    # -- clock helpers -------------------------------------------------------

    @property
    def clock(self) -> float:
        return self.sim.link.round_clock_s

    # -- fresh round ---------------------------------------------------------

    def run(self) -> RoundResult:
        with obs.span(obs.ROUND):
            return self._run()

    def _run(self) -> RoundResult:
        sim, server = self.sim, self.sim.server
        sim.link.mark_round_start()
        self._open_round_medium()
        # rejoin-with-stale-round: a client that left last round comes
        # back replaying its stale upload — rejected idempotently before
        # the round even opens, then resynced by this round's dissemination
        for cid in self.faults.rejoining(server.round):
            sim._push_stale_upload(cid)
        selected = server.select_clients()
        # late join: the client appears mid-round — it participates from
        # the NEXT round on (it gets the then-current global), this round
        # proceeds without it
        late = [c for c in selected
                if self.faults.is_late_join(c, server.round)]
        for cid in late:
            self._attr(cid, "late-join")
        cohort = [c for c in selected if c not in late]
        receivers, dissem_dropped = sim._disseminate(cohort)
        dissem_dropped = dissem_dropped + late
        t_model = self.clock          # everyone holds the model from here
        self._attribute_dissemination(cohort, receivers)
        for cid in receivers:
            sim._client_checkpoint(cid)   # durable installed-model state
        reporters, dropped, stopped, progress, ready = self._train_phase(
            receivers, t_model)
        # mid-round leave: trained, then left before uploading anything
        leavers = [c for c in reporters
                   if self.faults.leaves_mid_round(c, server.round)]
        if leavers:
            reporters = [c for c in reporters if c not in leavers]
            for cid in leavers:
                self._attr(cid, "churn")
            dropped = dropped + leavers
        dropped = dissem_dropped + dropped
        self.ctx = {
            "selected": selected, "reporters": reporters,
            "dropped": dropped, "stopped": stopped,
            "mean_train_loss": float(np.mean(  # accum-ok: reporting-only mean, not model state
                [p.metadata.train_loss for p in progress.values()]
            )) if progress else float("nan"),
            "mean_val_loss": float(np.mean(  # accum-ok: reporting-only mean, not model state
                [p.metadata.val_loss for p in progress.values()]
            )) if progress else float("nan"),
            # recorded into every aggregation snapshot: a restarted
            # server re-collects in the same chunk encoding and folds
            # against the same residual base
            "chunk_encoding": (sim.chunk_encoding.value
                               if sim.chunk_elems is not None else None),
            "residual": bool(sim.residual_uplink),
        }
        return self._collect_and_finish(ready, recovered=False)

    # -- resumed round (server restarted mid-collection) ---------------------

    def resume(self) -> RoundResult | None:
        """Continue the current round from its aggregation snapshot.

        The restarted server re-NACKs (re-collects) only the reporters
        the completion bitmap marks unfinished; clients that already
        folded are skipped entirely — if one re-uploads anyway (it never
        heard the round close), the fold is ignored idempotently.
        Returns None when no snapshot exists for the current round.
        """
        with obs.span(obs.ROUND):
            return self._resume()

    def _resume(self) -> RoundResult | None:
        sim = self.sim
        state = load_agg_snapshot(sim.server)
        if state is None:
            return None
        self.ctx = {k: state[k] for k in
                    ("selected", "reporters", "dropped", "stopped",
                     "mean_train_loss", "mean_val_loss",
                     "chunk_encoding", "residual")}
        self.folded = list(state["folded"])
        sim.link.mark_round_start()
        sim._round_medium = None     # uplink-only resume: fresh medium
        # post-restart, unfinished clients are ready immediately: their
        # training finished in the previous server's lifetime
        ready = {cid: 0.0 for cid in self.ctx["reporters"]}
        return self._collect_and_finish(ready, recovered=True)

    # -- phases --------------------------------------------------------------

    def _open_round_medium(self) -> None:
        """When the sim runs its downlink on the medium, create ONE
        ``SharedMedium`` for the whole round: dissemination, feedback and
        (interleaved) uplink share its clock, RNG, and fault schedule."""
        sim = self.sim
        sim._round_medium = None
        if getattr(sim, "downlink_mode", "link") != "medium":
            return
        sim._round_medium = SharedMedium(
            seed=(sim._seed, sim.server.round),
            frame_drop_prob=sim.link.drop_prob,
            reorder_prob=sim.uplink_reorder_prob,
            turnaround_s=sim.uplink_turnaround_s,
            chunk_drop=self.faults.as_chunk_drop() or sim.link.chunk_drop,
            faults=self.faults,
            arbitration=sim.arbitration, radio=sim.radio)

    def _attribute_dissemination(self, cohort, receivers) -> None:
        """Name why each cohort member did (not) come out of dissemination
        holding the model: download crash (resumed or not) vs plain loss."""
        sim = self.sim
        for cid in cohort:
            if cid in receivers:
                if cid in sim._downlink_resumed:
                    self._attr(cid, "crash-resumed")
                continue
            crash = self.faults.client_crash(cid)
            if crash is not None and crash.phase == "download":
                self._attr(cid, "crash")
            else:
                self._attr(cid, "link")

    def _train_phase(self, receivers, t_model):
        sim, server = self.sim, self.sim.server
        policy = self.policy
        reporters, dropped, stopped = [], [], []
        progress, ready = {}, {}
        for cid in receivers:
            client = sim.clients[cid]
            # draw first so the RNG stream is identical with/without an
            # injected crash (the differential recovery oracle needs the
            # fault-free and faulted runs to agree on dropout verdicts)
            node_failed = sim._rng.random() < client.dropout_prob
            crash = self.faults.client_crash(cid)
            if crash is not None and crash.phase == "train":
                # a resumable crash reboots + restores the durable
                # post-install checkpoint, then retrains — training is
                # deterministic in (seed, client, round), so the resumed
                # update is bit-identical to the crash-free one
                if not (crash.resume and sim.restart_client(cid)):
                    self._attr(cid, "crash")
                    dropped.append(cid)   # died before reporting anything
                    continue
                self._attr(cid, "crash-resumed")
            if node_failed:
                self._attr(cid, "node")
                dropped.append(cid)   # node failure this round
                continue
            upd = client.train_locally()
            sim._client_checkpoint(cid)   # durable trained-model state
            t0 = self.clock
            with obs.span(obs.REPORT):
                ring = sim._send(upd.to_cbor_segments(),
                                 "FL_Local_DataSet_Update",
                                 "fl/progress", Code.CONTENT)
                if ring is not None:
                    upd = type(upd).from_cbor_segments(ring)
            if ring is None:
                self._attr(cid, "link")
                dropped.append(cid)   # report lost on the link
                continue
            progress[cid] = upd
            ready[cid] = (t_model
                          + policy.train_time_s * client.straggler_factor
                          + (self.clock - t0))
            if not server.observe_ready(upd):
                continue
            if server.check_stop_condition(upd, cid):
                stopped.append(cid)
            reporters.append(cid)
        return reporters, dropped, stopped, progress, ready

    def _collect_and_finish(self, ready: dict[int, float],
                            *, recovered: bool) -> RoundResult:
        sim, server = self.sim, self.sim.server
        selected = self.ctx["selected"]
        reporters = self.ctx["reporters"]
        dropped = list(self.ctx["dropped"])
        deadline = self.policy.deadline_s
        quorum_pre = server.quorum_met(len(reporters), len(selected))
        installed = False
        if reporters and quorum_pre:
            if not recovered:
                server.begin_aggregation(
                    residual_base=(sim._residual_ref
                                   if self.ctx.get("residual") else None))
                # 0-fold snapshot: a crash before the first fold must
                # still resume (the reporter set is what it preserves)
                self._snapshot()
            pending = [c for c in reporters if c not in self.folded]
            if sim.chunk_elems is None:
                self._collect_monolithic(pending, ready, dropped)
            elif sim.uplink_mode == "interleaved":
                self._collect_interleaved(pending, ready, dropped)
            else:
                self._collect_sequential(pending, ready, dropped)
            quorum_final = server.quorum_met(len(self.folded), len(selected))
            # legacy semantics with no deadline: install whatever arrived
            # (the pre-quorum gate already passed); with a deadline the
            # quorum re-check *at the deadline* decides
            installed = (quorum_final if deadline is not None
                         else bool(self.folded))
            with obs.span(obs.FINALIZE):
                if installed:
                    server.finalize_aggregation()
                    self._snapshot(finalized=True)
                else:
                    server.abort_aggregation()
                    clear_agg_snapshot(server)
        quorum_met = (installed if (reporters and quorum_pre)
                      else quorum_pre)
        if not quorum_pre:
            for cid in reporters:
                self._attr(cid, "missed-quorum")
        elif reporters and not installed:
            for cid in self.folded:
                self._attr(cid, "missed-quorum")
        for cid in self.stragglers:
            self._attr(cid, "deadline")
        result = RoundResult(
            round=server.round, participants=list(selected),
            reporters=sorted(self.folded),
            dropped=sorted(set(dropped)),
            stopped=list(self.ctx["stopped"]),
            mean_train_loss=self.ctx["mean_train_loss"],
            mean_val_loss=self.ctx["mean_val_loss"],
            stragglers=sorted(set(self.stragglers)),
            quorum_met=quorum_met,
            recovered=recovered,
            clock_s=self.clock,
            snapshot_bytes=self.snapshot_bytes,
            fault_attribution=dict(sorted(self.attribution.items())),
        )
        self._count_frames(self.sim._round_medium)
        with obs.span(obs.FINALIZE):
            clear_agg_snapshot(server)  # the round is over either way
            self.sim._round_medium = None   # the round's fault domain closes
            server.finish_round(result)
        return result

    @staticmethod
    def _count_frames(medium) -> None:
        """Copy a round medium's frame counts into the round's record.
        ``frames_built`` (frames whose full CoAP message was built, counted
        as they are) is put beside them, at 0 when every frame took the
        arithmetic path."""
        if medium is not None:
            obs.count("frames_sent", medium.frames_sent)
            obs.count("frames_lost", medium.frames_lost)
            obs.count("frames_built", 0)

    # -- folding (shared by every uplink mode) -------------------------------

    def _fold(self, cid: int, flat: np.ndarray, dataset_size: int) -> bool:
        with obs.span(obs.SERVER_FOLD):
            return self._fold_one(cid, flat, dataset_size)

    def _fold_one(self, cid: int, flat: np.ndarray,
                  dataset_size: int) -> bool:
        server = self.sim.server
        if server.already_folded(cid):
            # duplicate re-fold (a resumed round re-receiving an upload
            # the snapshot already contains): ignored idempotently
            self.duplicate_folds += 1
            server.release_update_buffer(flat)
            return False
        server.accumulate_update(cid, flat, dataset_size)
        self.folded.append(cid)
        self._snapshot()
        # the snapshot for this fold is durable before the crash check
        # fires, so recovery never loses an acknowledged fold
        self.faults.check_server_crash(server.round, len(self.folded))
        return True

    def _snapshot(self, *, finalized: bool = False) -> None:
        server = self.sim.server
        if (server.ckpt is None or not self.policy.snapshot_aggregation
                or (server._agg is None and not finalized)):
            return
        if finalized:
            # keep only the marker state: finalize consumed the
            # accumulator, so rewrite the *existing* snapshot's meta via
            # a tombstone write guarding the finalize->checkpoint window
            server.ckpt.delete_named(_snapshot_name(server.round))
            return
        self.snapshot_bytes += save_agg_snapshot(server, self.ctx)  # accum-ok: int byte counter, not float accumulation

    # -- per-mode collection -------------------------------------------------

    def _deadline_gate(self, cid: int, ready: dict[int, float]) -> bool:
        """Advance the clock to the client's start; True when the client
        may still transmit.

        Boundary contract (pinned): a transfer may not *start* at or
        after the deadline — ``start >= deadline_s`` makes the client a
        straggler before any airtime is spent.  A transfer *completing*
        exactly at the deadline still counts: ``_missed_deadline`` is
        strict (``clock > deadline_s``).  The interleaved scheduler's
        ``medium.clock >= deadline_s`` window gate applies the same
        start-side rule on the shared clock."""
        deadline = self.policy.deadline_s
        start = max(self.clock, ready.get(cid, 0.0))
        if deadline is not None and start >= deadline:
            self.stragglers.append(cid)
            return False
        self.sim.link.advance_to_round(start)
        return True

    def _missed_deadline(self, cid: int) -> bool:
        deadline = self.policy.deadline_s
        if deadline is not None and self.clock > deadline:
            self.stragglers.append(cid)
            return True
        return False

    def _collect_monolithic(self, pending, ready, dropped) -> None:
        sim, server = self.sim, self.sim.server
        enc = server.cfg.params_encoding
        from repro.core.messages import FLLocalModelUpdate
        for cid in sorted(pending, key=lambda c: ready.get(c, 0.0)):
            crash = self.faults.client_crash(cid)
            if crash is not None and crash.phase in ("upload", "repair"):
                self._attr(cid, "crash")
                dropped.append(cid)   # died before/while answering the GET
                continue
            if not self._deadline_gate(cid, ready):
                continue
            payload = sim.clients[cid].local_model_update().to_cbor_segments(
                enc)
            with obs.span(obs.SCHED_UPLINK):
                ring = sim._send(payload, "FL_Local_Model_Update",
                                 "fl/model", Code.CONTENT)
            if ring is None:
                self._attr(cid, "link")
                dropped.append(cid)   # model transfer lost
                continue
            if self._missed_deadline(cid):
                continue              # arrived after the round closed
            with obs.span(obs.ASSEMBLE):
                upd = FLLocalModelUpdate.from_cbor_segments(ring)
            if upd.round != server.round or upd.model_id != server.model_id:
                self._attr(cid, "churn")
                dropped.append(cid)   # stale generation
                continue
            self._fold(cid, np.asarray(upd.params, dtype=np.float32),
                       sim.clients[cid].dataset_size())

    def _chunk_mode(self) -> tuple[str | None, bool]:
        """The chunk encoding + residual flag this round runs with — the
        snapshot-recorded values when resuming, the simulation defaults
        otherwise."""
        enc = self.ctx.get("chunk_encoding") or self.sim.chunk_encoding
        return enc, bool(self.ctx.get("residual",
                                      self.sim.residual_uplink))

    def _collect_sequential(self, pending, ready, dropped) -> None:
        sim = self.sim
        deadline = self.policy.deadline_s
        enc, residual = self._chunk_mode()
        for cid in sorted(pending, key=lambda c: ready.get(c, 0.0)):
            if not self._deadline_gate(cid, ready):
                continue
            crash = self.faults.client_crash(cid)
            resumable = (crash is not None
                         and crash.phase in ("upload", "repair")
                         and crash.resume
                         and sim.clients[cid].checkpoint_dir is not None)
            budget = None if deadline is None else deadline - self.clock
            with obs.span(obs.SCHED_UPLINK):
                flat = sim._collect_chunked(
                    cid, backoff=self.policy.backoff, faults=self.faults,
                    airtime_budget_s=budget, encoding=enc,
                    residual=residual, keep_partial=resumable)
            if (flat is None and resumable
                    and (deadline is None or self.clock < deadline)
                    and sim.restart_client(cid)):
                # reboot + restore the post-train checkpoint, then poll
                # the endpoint first: only the chunks it still misses go
                # back on the air (strictly fewer payload bytes)
                self._attr(cid, "crash-resumed")
                budget = None if deadline is None else deadline - self.clock
                with obs.span(obs.SCHED_UPLINK):
                    flat = sim._collect_chunked(
                        cid, backoff=self.policy.backoff,
                        faults=self.faults, airtime_budget_s=budget,
                        encoding=enc, residual=residual, poll_first=True,
                        resumed=True)
            if flat is None:
                if not self._missed_deadline(cid):
                    if crash is not None and crash.phase in ("upload",
                                                             "repair"):
                        self._attr(cid, "crash")
                    else:
                        self._attr(cid, "link")
                    dropped.append(cid)   # upload never completed
                continue
            if self._missed_deadline(cid):
                sim.server.release_update_buffer(flat)
                continue
            self._fold(cid, flat, sim.clients[cid].dataset_size())

    def _collect_interleaved(self, pending, ready, dropped) -> None:
        sim, server = self.sim, self.sim.server
        backoff = self.policy.backoff
        deadline = self.policy.deadline_s
        enc, residual = self._chunk_mode()
        sessions = []
        # each session frames and validates its chunks: scheduler work
        with obs.span(obs.SCHED_UPLINK):
            for cid in pending:
                crash = self.faults.client_crash(cid)
                kwargs = {"start_at": ready.get(cid, 0.0)}
                if backoff is not None:
                    kwargs["max_windows"] = backoff.max_windows
                if crash is not None and crash.phase in ("upload", "repair"):
                    kwargs["crash_at"] = (crash.crash_window,
                                          crash.at_frame or 0)
                sessions.append(sim.clients[cid].uplink_session(
                    sim.chunk_elems, server.uplink_endpoint(cid),
                    uri="fl/model/upload",
                    feedback_uri="fl/model/upload/fb",
                    encoding=enc, residual=residual, **kwargs))
        if not sessions:
            sim.last_medium_report = None
            sim.last_uplink_reports = []
            return
        if sim._round_medium is not None:
            # whole-round fault domain: dissemination already ran on this
            # medium, so the uplink contends on the same virtual clock,
            # RNG stream, and fault schedule
            medium = sim._round_medium
            start = min((s.start_at for s in sessions),
                        default=medium.clock)
            medium.advance_to(max(medium.clock, start))
        else:
            chunk_drop = self.faults.as_chunk_drop() or sim.link.chunk_drop
            medium = SharedMedium(
                seed=(sim._seed, server.round),
                frame_drop_prob=sim.link.drop_prob,
                reorder_prob=sim.uplink_reorder_prob,
                turnaround_s=sim.uplink_turnaround_s,
                chunk_drop=chunk_drop, faults=self.faults,
                arbitration=sim.arbitration, radio=sim.radio)
            # the uplink medium's clock continues the round clock:
            # sessions become ready when their owners finish training,
            # and the round deadline is absolute on the same axis
            medium.clock = min((s.start_at for s in sessions),
                               default=self.clock)
            medium.clock = max(medium.clock, 0.0)

        def fold(session) -> None:
            flat = server.pop_uplink(session.client_id)
            if flat is not None:
                self._fold(flat=flat, cid=session.client_id,
                           dataset_size=sim.clients[session.client_id]
                           .dataset_size())

        from repro.fl.chunking import run_interleaved_uplinks
        with obs.span(obs.SCHED_UPLINK):
            report = run_interleaved_uplinks(
                medium, sessions, record=sim._record_uplink,
                on_complete=fold, deadline_s=deadline, backoff=backoff,
                faults=self.faults, legacy=sim.legacy_scheduler)
        resume_cids = []
        for s in sessions:
            cid = s.client_id
            if cid in self.folded:
                continue
            crash = self.faults.client_crash(cid)
            crashed = bool(getattr(s, "crashed", False))
            if (crashed and crash is not None and crash.resume
                    and sim.clients[cid].checkpoint_dir is not None
                    and (deadline is None or medium.clock < deadline)
                    and sim.restart_client(cid)):
                # reboot + restore; the endpoint's partial reassembly is
                # kept in place so the resumed session polls it first
                resume_cids.append(cid)
                continue
            server.pop_uplink(cid)   # discard partial reassembly
            if s.expired:
                self.stragglers.append(cid)
            else:
                self._attr(cid, "crash" if crashed else "link")
                dropped.append(cid)
        resume_sessions = []
        if resume_cids:
            rkwargs = {}
            if backoff is not None:
                rkwargs["max_windows"] = backoff.max_windows
            with obs.span(obs.SCHED_UPLINK):
                for cid in resume_cids:
                    self._attr(cid, "crash-resumed")
                    resume_sessions.append(sim.clients[cid].uplink_session(
                        sim.chunk_elems, server.uplink_endpoint(cid),
                        uri="fl/model/upload",
                        feedback_uri="fl/model/upload/fb",
                        encoding=enc, residual=residual,
                        start_at=medium.clock, poll_first=True, **rkwargs))
                report2 = run_interleaved_uplinks(
                    medium, resume_sessions, record=sim._record_uplink,
                    on_complete=fold, deadline_s=deadline, backoff=backoff,
                    faults=self.faults, legacy=sim.legacy_scheduler)
            report2.per_client_done_s = {**report.per_client_done_s,
                                         **report2.per_client_done_s}
            # the resumed run re-derives energy over the whole medium
            # lifetime per client; earlier-only clients keep their rows
            report2.per_client_energy_j = {**report.per_client_energy_j,
                                           **report2.per_client_energy_j}
            report2.duty_cycle = {**report.duty_cycle,
                                  **report2.duty_cycle}
            report = report2
            for s in resume_sessions:
                cid = s.client_id
                if cid in self.folded:
                    continue
                server.pop_uplink(cid)
                if s.expired:
                    self.stragglers.append(cid)
                else:
                    self._attr(cid, "crash")
                    dropped.append(cid)
        if medium is not sim._round_medium:
            self._count_frames(medium)    # this collection's own medium
        sim.last_medium_report = report
        sim.last_uplink_reports = [s.report
                                   for s in sessions + resume_sessions]
        sim.last_uplink_report = (sim.last_uplink_reports[-1]
                                  if sim.last_uplink_reports else None)
        sim.link.advance_to_round(medium.clock)
