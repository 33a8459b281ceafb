"""Codec throughput: encode/decode µs per model size, plus BENCH_codec.json.

Compares the paths that exist in the system:
  * python_ref    — the pure-Python CBOR item encoder (oracle)
  * numpy_ta      — message encode via the contiguous fast path (one
                    payload copy into the preallocated buffer + finalize)
  * encode_vectored — scatter-gather message encode: owned header segments
                    + borrowed payload views, zero payload copies
  * decode_seed   — the seed decode chain: recursive oracle decode (payload
                    sliced to fresh bytes) + a ``bytes()`` copy before
                    ``np.frombuffer`` — kept inline as the baseline the
                    ISSUE's ≥3x decode criterion is measured against
  * decode_fastpath — iterative memoryview decode, ``np.frombuffer`` on the
                    zero-copy payload view
  * decode_segments — the segmented receive path: the same decode walking a
                    ``ScatterPayload``'s segment chain without joining it;
                    the params payload lands contiguous in one segment and
                    comes back as a borrowed view
  * decode_ring   — the *production* receive shape: ≤64 B blockwise
                    deliveries coalesced into a ``BlockReceiveRing`` arena,
                    decoded as borrowed views of the ring's own memory
  * pallas_f16    — the quantize_f16 kernel path emitting owned ``bytes``
  * pallas_f16_vec — the same kernel handing the wire a borrowed view,
                    spliced into a vectored message (no ``bytes`` handoff)
  * q8_kernel     — blockwise int8 compression kernel

The three kernel rows are host timings of the Pallas *interpreter* when
this runs on the CPU (as every committed ``BENCH_codec.json`` row was):
they time XLA's CPU backend running the kernel body, not the kernel on a
TPU, and are no device number.

``run()`` prints the CSV section; ``run_json()`` additionally returns the
machine-readable record (encode/decode MB/s, tracemalloc peak bytes, and a
``copies_per_roundtrip`` counter per model size) that ``benchmarks/run.py``
writes to ``BENCH_codec.json`` so the perf trajectory is tracked PR over PR.

``copies_per_roundtrip`` is measured, not asserted: tracemalloc peak bytes
of one encode + one decode divided by the payload size — ~2 for the
contiguous encode chain (encode buffer + finalize), ~0 for the vectored
chain (headers only on encode, views only on decode).
"""
from __future__ import annotations

import time
import tracemalloc
import uuid

import numpy as np

from repro.core import cbor, fastpath
from repro.core.messages import FLGlobalModelUpdate, ParamsEncoding
from repro.core.typed_arrays import decode_typed_array

UUID = uuid.UUID(bytes=bytes(range(16)))
SIZES = [1000, 10_000, 44_426, 1_000_000]


def _time(fn, repeats=9) -> float:
    """Best-of-N µs per call.  The minimum (not the mean) is the standard
    microbenchmark statistic: scheduler preemption and allocator jitter
    only ever add time, so min-of-N converges on the true cost and keeps
    the tier-2 trend gate from flapping on loaded boxes."""
    fn()  # warmup / jit
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _peak_alloc(fn) -> int:
    tracemalloc.start()
    fn()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return peak


def _decode_seed(data: bytes) -> np.ndarray:
    """The seed decode chain, verbatim: oracle decode (payload slice copy)
    then a bytes() round-trip into np.frombuffer (second copy)."""
    item = cbor.decode(data)
    ta = item[2]
    return np.frombuffer(bytes(ta.value), dtype="<f4")


def _decode_fastpath(data: bytes) -> np.ndarray:
    item = fastpath.decode(data)
    return decode_typed_array(item[2])


def _decode_segments(source) -> np.ndarray:
    item = fastpath.decode(source)      # segment cursor, no join
    return decode_typed_array(item[2])


def _ring_of(wire: bytes, block: int = 64):
    """The production receive shape: ≤64 B blockwise deliveries coalesced
    into a BlockReceiveRing's arena segments."""
    from repro.transport.coap import BlockReceiveRing

    ring = BlockReceiveRing()
    for i in range(0, len(wire), block):
        ring.add_block(wire[i : i + block])
    return ring


def _assemble_chunked(chunks) -> np.ndarray:
    """Receive side of a chunked transfer: gather every chunk payload into
    the assembler's preallocated model buffer (peak = model + O(chunk))."""
    from repro.fl.chunking import ChunkAssembler

    asm = ChunkAssembler()
    out = None
    for c in chunks:
        flat = asm.add(c)
        if flat is not None:
            out = flat
    return out


def _paths(n: int, flat: np.ndarray, msg: FLGlobalModelUpdate,
           wire_f32: bytes, sp_f32: fastpath.ScatterPayload, ring_f32,
           jflat) -> dict:
    from repro.kernels.q8_block.ops import compress_update
    from repro.kernels.quantize_f16.ops import (
        params_to_f16_payload,
        params_to_f16_view,
    )

    return {
        "python_ref_dynamic": (lambda: cbor.encode(
            [float(v) for v in flat[: min(n, 10_000)]]),
            min(n, 10_000) * 4),
        "numpy_ta_f16": (lambda: msg.to_cbor(ParamsEncoding.TA_F16), n * 4),
        "numpy_ta_f32": (lambda: msg.to_cbor(ParamsEncoding.TA_F32), n * 4),
        "encode_vectored_f32": (
            lambda: msg.to_cbor_segments(ParamsEncoding.TA_F32), n * 4),
        "decode_seed_f32": (lambda: _decode_seed(wire_f32), n * 4),
        "decode_fastpath_f32": (lambda: _decode_fastpath(wire_f32), n * 4),
        "decode_segments_f32": (lambda: _decode_segments(sp_f32), n * 4),
        "decode_ring_f32": (lambda: _decode_segments(ring_f32), n * 4),
        "pallas_f16": (lambda: params_to_f16_payload(jflat), n * 4),
        "pallas_f16_vec": (lambda: msg.to_cbor_segments(
            ParamsEncoding.TA_F16,
            params_payload=params_to_f16_view(jflat)), n * 4),
        "q8_kernel": (lambda: compress_update(jflat), n * 4),
    }


def run_json() -> tuple[list[str], dict]:
    """-> (CSV rows, BENCH_codec.json record)."""
    import jax.numpy as jnp

    from repro.kernels import interpret_mode

    rows = ["path,model_size,us_per_call,derived_MBps"]
    record: dict = {"bench": "codec_throughput", "unit": "MB/s", "sizes": {},
                    "kernel_rows": ("host timing of the Pallas interpreter, "
                                    "not a device number"
                                    if interpret_mode() else
                                    "compiled Pallas kernels, host clock")}
    rng = np.random.default_rng(0)
    for n in SIZES:
        flat = rng.standard_normal(n).astype(np.float32)
        jflat = jnp.asarray(flat)
        msg = FLGlobalModelUpdate(UUID, 1, flat, True)
        wire_f32 = msg.to_cbor(ParamsEncoding.TA_F32)
        sp_f32 = fastpath.ScatterPayload(
            msg.to_cbor_segments(ParamsEncoding.TA_F32))
        ring_f32 = _ring_of(wire_f32)

        entry: dict = {"bytes_f32_payload": n * 4}
        for name, (fn, nbytes) in _paths(n, flat, msg, wire_f32, sp_f32,
                                         ring_f32, jflat).items():
            us = _time(fn)
            rows.append(f"{name},{n},{us:.1f},{nbytes / us:.1f}")
            entry[name] = {"us_per_call": round(us, 1),
                           "MBps": round(nbytes / us, 1)}
        entry["speedup_decode_fastpath_vs_seed"] = round(
            entry["decode_seed_f32"]["us_per_call"]
            / entry["decode_fastpath_f32"]["us_per_call"], 2)
        entry["speedup_decode_segments_vs_seed"] = round(
            entry["decode_seed_f32"]["us_per_call"]
            / entry["decode_segments_f32"]["us_per_call"], 2)
        entry["speedup_encode_vectored_vs_contiguous"] = round(
            entry["numpy_ta_f32"]["us_per_call"]
            / entry["encode_vectored_f32"]["us_per_call"], 2)
        # peak allocations: "fastpath" tracks the production wire path —
        # since the vectored refactor that is the scatter-gather encoder
        # (headers only); the contiguous single-buffer path stays recorded
        # for comparison.
        peak_enc_vec = _peak_alloc(
            lambda: msg.to_cbor_segments(ParamsEncoding.TA_F32))
        peak_enc_contig = _peak_alloc(
            lambda: msg.to_cbor(ParamsEncoding.TA_F32))
        peak_dec = _peak_alloc(lambda: _decode_fastpath(wire_f32))
        entry["peak_alloc_encode_fastpath"] = peak_enc_vec
        entry["peak_alloc_encode_contiguous"] = peak_enc_contig
        entry["peak_alloc_decode_seed"] = _peak_alloc(
            lambda: _decode_seed(wire_f32))
        entry["peak_alloc_decode_fastpath"] = peak_dec
        # receiver peak of a full chunked transfer: the gather assembler
        # allocates one model buffer and writes each chunk into its slot,
        # so this stays ≈ bytes_f32_payload + O(chunk), not 2× model.
        from repro.fl.chunking import chunk_stream
        chunks = list(chunk_stream(UUID, 1, flat, 4096))
        _assemble_chunked(chunks)  # warmup
        entry["peak_alloc_decode_chunked"] = _peak_alloc(
            lambda: _assemble_chunked(chunks))
        entry["copies_per_roundtrip"] = {
            "contiguous": round((peak_enc_contig + peak_dec) / (n * 4), 2),
            "vectored": round((peak_enc_vec + peak_dec) / (n * 4), 2),
        }
        record["sizes"][str(n)] = entry
    return rows, record


def run() -> list[str]:
    rows, _ = run_json()
    return rows


if __name__ == "__main__":
    import json

    rows, record = run_json()
    print("\n".join(rows))
    print(json.dumps(record, indent=2))
