#!/usr/bin/env python3
"""Smoke run on one TPU chip: federated LeNet-5 rounds and the Pallas kernels.

    python chip_smoke.py [--seed 0]

Everything runs in this one process, which holds the chip; it starts no
child process.  Phases, in order:

1. ``fl_rounds`` — LeNet-5 (44,426 parameters) trained by 8 clients for
   3 rounds through ``FLServer`` / ``FLClient`` / ``FLSimulation``: q8
   residual chunked uplinks (4,096-element chunks) interleaved on the
   shared medium, downlink on the same medium, local training jitted on the
   chip.  Checks: quorum every round, finite losses, round-3 train loss
   below round 1's, trained leaves on a ``tpu`` device, and each round's
   new global model bit-identical to batch FedAvg (``fedavg_delta`` on the
   residual base) over that round's reassembled reporter updates.
2. ``kernel_chunks`` — ``chunk_stream(quantizer="kernel")`` against
   ``quantizer="numpy"`` for ``ta-float16le`` and ``q8-block`` at 44,426 and
   1,000,000 parameters: chunk CRCs and encoded bytes must be identical.
   The f16 input carries subnormals, ties, overflow, ±inf and NaN payloads.
3. ``fedavg_kernel`` — ``fedavg_aggregate`` against ``kernels/fedavg/ref.py``
   at K=8 and K=256 clients, n=1,000,000, within the oracle's tolerance.

One JSON object per line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero without that line when JAX finds no TPU, when
the repository's sources are missing, or when any phase fails: no phase's
exception is caught.  ``smoke_wall_s`` values are smoke timings on the
host clock, not metrics.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache``; the ``compile`` line reports the compile
seconds and whether the cache was warm.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import uuid
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

CLIENTS = 8
ROUNDS = 3
SAMPLES_PER_CLIENT = 200
CHUNK_ELEMS = 4096
KERNEL_SIZES = (44_426, 1_000_000)
FEDAVG_K = (8, 256)
FEDAVG_N = 1_000_000

# f32 bit patterns at every branch of the f32 -> f16 rounding
F16_EDGE_BITS = (
    0x00000000, 0x80000000, 0x00000001, 0x007FFFFF, 0x80400000,
    0x32FFFFFF, 0x33000000, 0x33000001, 0x337FFFFF, 0x33800000,
    0x33C00000, 0x34200000, 0x35500000, 0x387FE000, 0x387FF000,
    0x387FFFFF, 0x38800000, 0x3F801000, 0x3F803000, 0x477FEFFF,
    0x477FF000, 0x47800000, 0x7F7FFFFF, 0xC7800000, 0x7F800000,
    0xFF800000, 0x7FC00000, 0xFFC00001, 0x7F800001, 0x7F802000,
    0x7FBFFFFF, 0xFFFFFFFF,
)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def phase_fl_rounds(seed: int, platform: str) -> dict:
    from repro.core.params_codec import flatten_params
    from repro.data import partition_iid, synthetic_mnist
    from repro.fl import FLClient, FLServer, FLSimulation, OrchestrationConfig
    from repro.fl.aggregation import fedavg_delta
    from repro.models import lenet5
    from repro.train.optim import SGDConfig

    flat, spec = flatten_params(lenet5.init_params(jax.random.PRNGKey(seed)))
    check(flat.size == lenet5.PARAM_COUNT, f"LeNet-5 has {flat.size} params")
    shards = partition_iid(synthetic_mnist(CLIENTS * SAMPLES_PER_CLIENT,
                                           seed=seed), CLIENTS, seed=seed)
    clients = [FLClient(client_id=i, data=shards[i], loss_fn=lenet5.loss_fn,
                        spec=spec, local_epochs=1, batch_size=32,
                        sgd=SGDConfig(lr=0.05), seed=seed)
               for i in range(CLIENTS)]
    cfg = OrchestrationConfig(
        num_clients=CLIENTS, clients_per_round=CLIENTS, min_fraction=0.5,
        num_rounds=ROUNDS, min_local_samples=32, seed=seed)
    server = FLServer(cfg, flat)
    sim = FLSimulation(server, clients, seed=seed, chunk_elems=CHUNK_ELEMS,
                       chunk_encoding="q8-block", residual_uplink=True,
                       uplink_mode="interleaved", downlink_mode="medium")

    # observe the server's fold inputs: copies of the residual base and of
    # every reassembled update, before the gather buffers are recycled
    fold: dict = {}
    begin, accumulate = server.begin_aggregation, server.accumulate_update

    def recording_begin(*, residual_base=None):
        fold["base"] = np.array(residual_base, np.float32)
        fold["updates"] = []
        begin(residual_base=residual_base)

    def recording_accumulate(client_id, params, dataset_size):
        fold["updates"].append((client_id, np.array(params, np.float32),
                                dataset_size))
        accumulate(client_id, params, dataset_size)

    server.begin_aggregation = recording_begin
    server.accumulate_update = recording_accumulate

    losses = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        r = sim.run_round()
        wall = time.perf_counter() - t0
        check(r.quorum_met, f"round {r.round}: quorum missed")
        folded = sorted(cid for cid, _, _ in fold["updates"])
        check(folded == r.reporters,
              f"round {r.round}: folded {folded} != reporters {r.reporters}")
        ups = sorted(fold["updates"])
        ref = fedavg_delta(fold["base"], [u for _, u, _ in ups],
                           [s for _, _, s in ups])
        identical = ref.tobytes() == server.global_params.tobytes()
        check(bool(np.isfinite([r.mean_train_loss, r.mean_val_loss]).all()),
              f"round {r.round}: non-finite loss")
        emit(phase="fl_rounds", round=r.round, reporters=len(r.reporters),
             dropped=len(r.dropped), quorum_met=r.quorum_met,
             train_loss=r.mean_train_loss, val_loss=r.mean_val_loss,
             global_bit_identical_to_batch_fedavg=identical,
             smoke_wall_s=wall)
        check(identical, f"round {r.round}: global model differs from "
                         f"batch FedAvg over the reassembled updates")
        losses.append(r.mean_train_loss)
    check(losses[-1] < losses[0],
          f"train loss did not fall: round 1 {losses[0]}, round "
          f"{ROUNDS} {losses[-1]}")
    leaves = [leaf for c in clients if c.params is not None
              for leaf in jax.tree.leaves(c.params)]
    check(bool(leaves) and all(isinstance(x, jax.Array) for x in leaves),
          "trained leaves are not device arrays")
    platforms = sorted({d.platform for x in leaves for d in x.devices()})
    check(platforms == [platform], f"trained leaves live on {platforms}")
    return {"params": int(flat.size), "clients": CLIENTS, "rounds": ROUNDS,
            "leaf_platforms": platforms}


def phase_kernel_chunks(seed: int) -> None:
    from repro.core.params_codec import Q8_BLOCK, quantize_q8
    from repro.fl.chunking import chunk_stream
    from repro.kernels.q8_block.ops import q8_chunk_arrays
    from repro.kernels.quantize_f16.ops import params_to_f16_array

    rng = np.random.default_rng(seed)
    model_id = uuid.UUID(int=seed)
    for n in KERNEL_SIZES:
        nblocks = -(-n // Q8_BLOCK)
        # q8: finite values at per-block magnitudes 1e-4..1e3, one zero
        # block, and two blocks of exact rounding ties (scale 1 and 2^-10)
        mag = np.repeat(10.0 ** rng.uniform(-4, 3, nblocks), Q8_BLOCK)[:n]
        q8_in = (rng.standard_normal(n) * mag).astype(np.float32)
        ties = np.concatenate([[127.0], np.arange(-127, 127) + 0.5,
                               [-3.5]]).astype(np.float32)
        q8_in[Q8_BLOCK:2 * Q8_BLOCK] = 0.0
        q8_in[2 * Q8_BLOCK:3 * Q8_BLOCK] = ties
        q8_in[3 * Q8_BLOCK:4 * Q8_BLOCK] = ties * np.float32(2.0 ** -10)
        # f16: magnitudes from below the f16 subnormals to past its max,
        # plus every special bit pattern
        f16_in = (rng.standard_normal(n)
                  * 10.0 ** rng.uniform(-9, 5.5, n)).astype(np.float32)
        edge = np.array(F16_EDGE_BITS, np.uint32).view(np.float32)
        f16_in[:edge.size] = edge

        for enc, x in (("ta-float16le", f16_in), ("q8-block", q8_in)):
            with np.errstate(over="ignore"):
                kern = list(chunk_stream(model_id, 0, x, CHUNK_ELEMS,
                                         encoding=enc, quantizer="kernel"))
                host = list(chunk_stream(model_id, 0, x, CHUNK_ELEMS,
                                         encoding=enc, quantizer="numpy"))
                check(len(kern) == len(host), f"{enc} n={n}: chunk counts")
                crc_diff = sum(a.crc32 != b.crc32 for a, b in zip(kern, host))
                byte_diff = sum(a.to_cbor() != b.to_cbor()
                                for a, b in zip(kern, host))
                if enc == "q8-block":
                    q_k, s_k, _ = q8_chunk_arrays(x)
                    q_h, s_h, _ = quantize_q8(x, Q8_BLOCK)
                    elems = {"q_values_differing": int((q_k != q_h).sum()),
                             "scales_differing": int(
                                 (s_k.view(np.uint32)
                                  != s_h.view(np.uint32)).sum())}
                else:
                    bits_k = params_to_f16_array(x).view(np.uint16)
                    bits_h = x.astype("<f2").view(np.uint16)
                    elems = {"f16_bits_differing":
                             int((bits_k != bits_h).sum())}
            emit(phase="kernel_chunks", encoding=enc, n=n, chunks=len(kern),
                 chunks_crc_differing=int(crc_diff),
                 chunks_bytes_differing=int(byte_diff), **elems)
            check(crc_diff == 0 and byte_diff == 0 and
                  not any(elems.values()),
                  f"{enc} n={n}: kernel and numpy chunk payloads differ")


def phase_fedavg_kernel(seed: int) -> None:
    from repro.kernels.fedavg.ops import fedavg_aggregate
    from repro.kernels.fedavg.ref import ATOL, RTOL, fedavg_ref

    key = jax.random.PRNGKey(seed)
    for k in FEDAVG_K:
        ku, kw, key = jax.random.split(key, 3)
        # made on the device: 1 GB of updates at K=256 never crosses PCIe
        updates = jax.random.normal(ku, (k, FEDAVG_N), jax.numpy.float32)
        sizes = jax.random.randint(kw, (k,), 1, 500).astype(
            jax.numpy.float32)
        out = fedavg_aggregate(updates, sizes)
        ref = np.asarray(fedavg_ref(updates, sizes))
        err = np.abs(out - ref)
        within = bool(np.all(err <= ATOL + RTOL * np.abs(ref)))
        emit(phase="fedavg_kernel", k=k, n=FEDAVG_N,
             max_abs_err=float(err.max()), rtol=RTOL, atol=ATOL,
             within_tolerance=within)
        check(within, f"fedavg K={k}: outside the ref.py tolerance")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    from repro import compile_cache, obs

    cache_dir = compile_cache.enable(ROOT)
    entries_before = (sum(1 for _ in cache_dir.iterdir())
                      if cache_dir.is_dir() else 0)
    before = obs.compile_totals()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit(phase="device", **device)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    t_all = time.perf_counter()
    for name, run in (
            ("fl_rounds", lambda: phase_fl_rounds(args.seed, dev.platform)),
            ("kernel_chunks", lambda: phase_kernel_chunks(args.seed)),
            ("fedavg_kernel", lambda: phase_fedavg_kernel(args.seed))):
        t0 = time.perf_counter()
        summary = run() or {}
        emit(phase=name, ok=True, **summary,
             smoke_wall_s=time.perf_counter() - t0)

    after = obs.compile_totals()
    got = {k: after[k] - before[k] for k in after}
    emit(phase="compile", cache_dir=str(cache_dir),
         cache_entries_before=entries_before,
         cache_hits=got["cache_hits"], cache_writes=got["cache_writes"],
         cache_warm=got["cache_hits"] > 0 and got["cache_writes"] == 0,
         backend_compiles=got["compiles"], compile_s=got["compile_s"],
         smoke_wall_s=time.perf_counter() - t_all)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
