"""FL client: local SGD training + TinyFL message handling (paper §V).

The client holds a local train/validation split, trains the received global
model for E local epochs, reports `FL_Local_DataSet_Update` notifications via
the observe mechanism, and answers the final GET with `FL_Local_Model_Update`.
"""
from __future__ import annotations

import uuid
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.messages import (
    FLChunkAck,
    FLChunkNack,
    FLGlobalModelUpdate,
    FLLocalDataSetUpdate,
    FLLocalModelUpdate,
    FLModelChunk,
    ModelMetadata,
    ParamsEncoding,
)
from repro.fl.chunking import ChunkAssembler, UplinkSession, chunk_stream
from repro.core.params_codec import (
    ErrorFeedback,
    ParamsSpec,
    flatten_params,
    unflatten_params,
)
from repro.train.optim import SGDConfig, sgd_update

# dtype per durable-checkpoint leaf name: the restore tree is rebuilt from
# the header's ``leaves`` list (layouts vary with what the client held when
# it checkpointed), and the checkpoint codec casts each leaf to its
# reference dtype — so the mapping here is the whole layout contract.
_CLIENT_LEAF_DTYPES = {
    "asm_buf": "<f4",        # partial downlink gather buffer
    "asm_received": "<i4",   # received chunk-index bitmap
    "ef_prev": "<f4",        # error-feedback replay residual (round start)
    "ef_res": "<f4",         # live error-feedback residual
    "global": "<f4",         # installed global reference (residual uplinks)
    "params": "<f4",         # local model, flattened
}


@dataclass
class FLClient:
    client_id: int
    data: dict                       # {"images"/..., "labels"}
    loss_fn: Callable                # (params, batch) -> (loss, metrics)
    spec: ParamsSpec
    local_epochs: int = 1
    batch_size: int = 32
    val_fraction: float = 0.2
    sgd: SGDConfig = field(default_factory=SGDConfig)
    seed: int = 0
    dropout_prob: float = 0.0        # node-failure simulation
    straggler_factor: float = 1.0    # >1 -> reports late
    encoding: ParamsEncoding = ParamsEncoding.TA_F32
    error_feedback: ErrorFeedback = field(default_factory=ErrorFeedback)
    # durable storage root for crash-resume (``save_client_state``); None
    # means a crash loses everything (pure dropout, the pre-PR behaviour)
    checkpoint_dir: str | None = None

    params: dict | None = None
    round: int = 0
    model_id: uuid.UUID | None = None
    samples_seen: int = 0
    # the flat f32 global this client installed (what a residual uplink
    # diffs against — the *received* reference, i.e. the dequantized model
    # under a lossy downlink encoding, exactly what the server folds onto)
    last_global_flat: np.ndarray | None = field(default=None, repr=False)
    _train_idx: np.ndarray = field(init=False, repr=False, default=None)
    _val_idx: np.ndarray = field(init=False, repr=False, default=None)
    _assembler: ChunkAssembler = field(init=False, repr=False,
                                       default_factory=ChunkAssembler)
    # error-feedback replay state: re-generating the same round's chunk
    # stream (a restarted server re-collecting this client) must restart
    # from the residual the round *began* with, or the re-upload would
    # not be bit-identical to the original
    _ef_round: int | None = field(init=False, repr=False, default=None)
    _ef_prev: np.ndarray | None = field(init=False, repr=False, default=None)
    _ckpt_mgr: object = field(init=False, repr=False, default=None)

    def __post_init__(self) -> None:
        # the client knows its own model size: bound chunk-reassembly
        # allocations to it (a forged num-chunks cannot inflate the
        # gather buffer past one model)
        self._assembler = ChunkAssembler(expected_elems=self.spec.total)
        n = len(self.data["labels"])
        rng = np.random.default_rng((self.seed, self.client_id))
        perm = rng.permutation(n)
        n_val = max(1, int(n * self.val_fraction))
        self._val_idx, self._train_idx = perm[:n_val], perm[n_val:]
        self._grad_fn = jax.jit(jax.value_and_grad(
            lambda p, b: self.loss_fn(p, b)[0]))
        self._eval_fn = jax.jit(lambda p, b: self.loss_fn(p, b)[0])

    # -- message handlers (server-driven CoAP semantics) ---------------------

    def handle_global_model(self, msg: FLGlobalModelUpdate) -> None:
        """POST /fl/model — install the new global model.

        ``np.asarray`` instead of ``astype``: a chunk-assembled model is
        already the receiver-owned f32 gather buffer, so installing it
        costs only the per-leaf unflatten casts, not an extra whole-model
        copy."""
        flat = np.asarray(msg.params, dtype=np.float32)
        self.params = unflatten_params(flat, self.spec)
        # keep the installed reference for residual uplinks (flat is the
        # client-owned gather buffer / decoded vector; nothing recycles it)
        self.last_global_flat = flat.reshape(-1)
        self.round = msg.round
        self.model_id = msg.model_id
        self.samples_seen = 0
        self.training_enabled = msg.continue_training

    def handle_model_chunk(self, msg: FLModelChunk) -> bool:
        """POST /fl/model/chunk — one slice of a chunked global model.

        Verifies the chunk's CRC32 (over its little-endian f32 payload),
        buffers it, and installs the assembled model once every chunk of
        the (model_id, round) generation has arrived.  Returns True on
        install.  A chunk from a newer round discards stale buffers (a
        client that missed the end of one round resynchronizes on the
        next), while a retransmitted chunk of an older — or the already
        installed — generation is dropped as a duplicate without touching
        in-progress assembly (see ``ChunkAssembler``).
        """
        flat = self._assembler.add(msg)
        if flat is None:
            return False
        self.handle_global_model(FLGlobalModelUpdate(
            model_id=msg.model_id, round=msg.round, params=flat,
            continue_training=True))
        return True

    # engine-facing aliases: the selective-repeat loop (fl.chunking) drives
    # any receiver through receive_chunk / chunk_feedback.
    receive_chunk = handle_model_chunk

    def chunk_feedback(self, model_id: uuid.UUID, round_: int,
                       num_chunks: int) -> FLChunkAck | FLChunkNack:
        """Selective-repeat feedback for the given downlink generation:
        ACK when fully assembled/installed, else NACK the missing set.

        The installed-generation check matters after a crash-restore: the
        rebuilt assembler has no completed-key memory, but a client whose
        durable checkpoint already holds the installed model for exactly
        this generation must ACK, not re-download a model it has."""
        if (self.params is not None and model_id == self.model_id
                and round_ == self.round):
            return FLChunkAck(model_id, round_, num_chunks)
        return self._assembler.feedback(model_id, round_, num_chunks)

    # -- durable client state (crash-resume) ---------------------------------

    def _ckpt(self):
        if self._ckpt_mgr is None:
            from repro.checkpoint.cbor_checkpoint import CheckpointManager
            self._ckpt_mgr = CheckpointManager(
                Path(self.checkpoint_dir) / f"client_{self.client_id:04d}")
        return self._ckpt_mgr

    def save_client_state(self) -> None:
        """Persist everything a resumed round needs to be bit-identical to
        a crash-free one (docs/fault_model.md, client-checkpoint format):
        installed params + the residual reference ``last_global_flat``,
        the error-feedback replay pair (``_ef_round``/``_ef_prev``) and
        live residual, and any in-progress downlink assembly.  One named
        checkpoint, atomically replaced (tmp-then-rename) — the client
        mirror of the server's ``save_agg_snapshot``.  No-op without a
        ``checkpoint_dir``."""
        if self.checkpoint_dir is None:
            return
        tree: dict[str, np.ndarray] = {}
        meta: dict = {
            "round": int(self.round),
            "model_id": str(self.model_id) if self.model_id else "",
            "samples_seen": int(self.samples_seen),
            "ef_round": -1 if self._ef_round is None else int(self._ef_round),
        }
        if self.params is not None:
            flat, _ = flatten_params(self.params)
            tree["params"] = np.ascontiguousarray(flat, dtype="<f4")
        if self.last_global_flat is not None:
            tree["global"] = np.ascontiguousarray(self.last_global_flat,
                                                  dtype="<f4")
        if self._ef_prev is not None:
            tree["ef_prev"] = np.ascontiguousarray(self._ef_prev,
                                                   dtype="<f4")
        if self.error_feedback.residual is not None:
            tree["ef_res"] = np.ascontiguousarray(
                self.error_feedback.residual, dtype="<f4")
        asm = self._assembler.export_state()
        if asm is not None:
            tree["asm_buf"] = asm.pop("buf")
            tree["asm_received"] = asm.pop("received")
            meta["asm"] = asm       # generation key + geometry scalars
        meta["leaves"] = sorted(tree)
        self._ckpt().save_named("client_state", tree, round_=self.round,
                                meta=meta)

    def try_restore_client(self) -> bool:
        """Rebuild this client from its durable checkpoint after
        ``simulate_crash``.  Header-first restore: the saved leaf layout
        varies (a pre-install crash has no params; a mid-download crash
        carries assembler state), so the header's ``leaves`` list shapes
        the restore tree.  Returns False — leaving the client a plain
        dropout — when there is no directory, no checkpoint, or a torn /
        unrecognised one."""
        if self.checkpoint_dir is None:
            return False
        mgr = self._ckpt()
        hdr = mgr.peek_named("client_state")
        if hdr is None:
            return False
        names = [str(n) for n in (hdr.get("meta") or {}).get("leaves", [])]
        if any(n not in _CLIENT_LEAF_DTYPES for n in names):
            return False        # future/foreign layout: not restorable
        tree_like = {n: np.empty(0, dtype=_CLIENT_LEAF_DTYPES[n])
                     for n in names}
        out = mgr.restore_named("client_state", tree_like)
        if out is None:
            return False
        tree, header = out
        meta = header.get("meta") or {}
        self.round = int(meta.get("round", 0))
        mid = str(meta.get("model_id", ""))
        self.model_id = uuid.UUID(mid) if mid else None
        self.samples_seen = int(meta.get("samples_seen", 0))
        efr = int(meta.get("ef_round", -1))
        self._ef_round = None if efr < 0 else efr

        def _flat(name: str) -> np.ndarray | None:
            arr = tree.get(name)
            if arr is None:
                return None
            return np.ascontiguousarray(arr, dtype="<f4").reshape(-1)

        flat = _flat("params")
        self.params = (None if flat is None
                       else unflatten_params(flat, self.spec))
        self.last_global_flat = _flat("global")
        self._ef_prev = _flat("ef_prev")
        self.error_feedback = ErrorFeedback(residual=_flat("ef_res"))
        self._assembler = ChunkAssembler(expected_elems=self.spec.total)
        asm = meta.get("asm")
        if asm is not None and "asm_buf" in tree:
            st = dict(asm)
            st["buf"] = tree["asm_buf"]
            st["received"] = tree["asm_received"]
            try:
                self._assembler.restore_state(st)
            except (ValueError, KeyError, TypeError):
                pass    # garbage assembler snapshot: re-download from NACK
        if self.params is not None:
            self.training_enabled = True
        return True

    def simulate_crash(self) -> None:
        """Wipe every piece of volatile state — what a device reboot
        loses.  The durable checkpoint (if any) survives on disk;
        ``try_restore_client`` brings it back."""
        self.params = None
        self.round = 0
        self.model_id = None
        self.samples_seen = 0
        self.last_global_flat = None
        self._assembler = ChunkAssembler(expected_elems=self.spec.total)
        self._ef_round = None
        self._ef_prev = None
        self.error_feedback = ErrorFeedback()
        self.training_enabled = False

    def local_model_chunks(self, chunk_elems: int, *,
                           encoding: ParamsEncoding | str =
                           ParamsEncoding.TA_F32,
                           residual: bool = False) -> list[FLModelChunk]:
        """The local model update as a chunked uplink stream — the same
        ``FLModelChunk`` framing as the downlink, in reverse.

        ``encoding`` picks the chunk wire format (f32 / f16 / q8-block);
        lossy encodings run through this client's ``error_feedback`` so
        the quantization error of round t is added back in round t+1.
        ``residual`` transmits ``local − last_global`` (the reference
        installed by ``handle_global_model``) instead of the raw weights —
        the server folds the deltas against its own copy of that
        reference.  Re-generating the stream for the *same* round (a
        restarted server re-collecting this client) replays the round's
        starting error-feedback residual, so the re-upload is
        bit-identical to the original."""
        if self.params is None:
            raise RuntimeError("no local model to upload")
        if isinstance(encoding, str):
            encoding = ParamsEncoding(encoding)
        with obs.span(obs.CLIENT_ENCODE):
            flat, _ = flatten_params(self.params)
            if residual:
                if self.last_global_flat is None:
                    raise RuntimeError("no installed global model to diff "
                                       "against for a residual uplink")
                if self.last_global_flat.size != flat.size:
                    raise ValueError("residual reference does not match "
                                     "the local model size")
                flat = flat - self.last_global_flat
            ef = None
            if encoding in (ParamsEncoding.TA_F16, ParamsEncoding.Q8):
                ef = self.error_feedback
                if self._ef_round == self.round:
                    ef.residual = self._ef_prev      # same-round replay
                else:
                    self._ef_round = self.round
                    self._ef_prev = ef.residual
            return list(chunk_stream(self.model_id, self.round, flat,
                                     chunk_elems, encoding=encoding,
                                     error_feedback=ef))

    def uplink_session(self, chunk_elems: int, receiver, *,
                       encoding: ParamsEncoding | str =
                       ParamsEncoding.TA_F32,
                       residual: bool = False,
                       **kwargs) -> UplinkSession:
        """This client's chunked upload as a schedulable state machine —
        what the shared-medium scheduler interleaves across clients
        (``fl.chunking.run_interleaved_uplinks``).  ``receiver`` is the
        server-side reassembly endpoint for this client; ``encoding`` and
        ``residual`` select the chunk wire format (``local_model_chunks``)."""
        return UplinkSession(self.client_id,
                             self.local_model_chunks(chunk_elems,
                                                     encoding=encoding,
                                                     residual=residual),
                             receiver, **kwargs)

    def dataset_size(self) -> int:
        return len(self._train_idx)

    def train_locally(self) -> FLLocalDataSetUpdate:
        """Run E local epochs; returns the observe notification payload."""
        if self.params is None:
            raise RuntimeError("no global model installed")
        with obs.span(obs.CLIENT_TRAIN):
            # the installed global's host leaves go to the device once,
            # here, instead of implicitly inside the first jitted step
            obs.h2d(jax.tree.leaves(self.params))
            self.params = jax.device_put(self.params)
            rng = np.random.default_rng((self.seed, self.client_id,
                                         self.round))
            opt_state: dict = {}
            n = len(self._train_idx)
            for _ in range(self.local_epochs):
                order = rng.permutation(n)
                for start in range(0, n - self.batch_size + 1,
                                   self.batch_size):
                    idx = self._train_idx[order[start:start
                                                + self.batch_size]]
                    _, grads = self._grad_fn(self.params, self._batch(idx))
                    self.params, opt_state = sgd_update(
                        self.params, grads, opt_state, self.sgd)
                    self.samples_seen += self.batch_size
                    obs.count("train_steps", 1)
            return self.progress_update()

    def progress_update(self) -> FLLocalDataSetUpdate:
        return FLLocalDataSetUpdate(
            dataset_size=self.samples_seen,
            metadata=ModelMetadata(*self._losses()))

    def _losses(self) -> tuple[float, float]:
        tl = self._eval(self._train_idx[:256])
        vl = self._eval(self._val_idx[:256])
        return float(tl), float(vl)

    def _batch(self, idx: np.ndarray) -> dict:
        """The rows ``idx`` of this client's data, copied to the device."""
        host = {k: v[idx] for k, v in self.data.items()}
        obs.h2d(host.values())
        return {k: jnp.asarray(v) for k, v in host.items()}

    def _eval(self, idx: np.ndarray) -> float:
        loss = self._eval_fn(self.params, self._batch(idx))
        with obs.span(obs.WAIT):
            loss.block_until_ready()
        obs.d2h([loss])
        return float(loss)

    def local_model_update(self) -> FLLocalModelUpdate:
        """GET /fl/model — reply with the locally-trained model."""
        flat, _ = flatten_params(self.params)
        tl, vl = self._losses()
        return FLLocalModelUpdate(
            model_id=self.model_id, round=self.round, params=flat,
            metadata=ModelMetadata(tl, vl))
