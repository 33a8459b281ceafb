# One function per paper table. Prints CSV sections; also writes
# BENCH_codec.json (codec MB/s + peak allocations + copies_per_roundtrip)
# so the serialization perf trajectory is tracked from PR to PR.
#
# `--check` compares a fresh codec run against the committed
# BENCH_codec.json and exits non-zero on a >2x decode- OR
# encode-throughput regression — the PR-over-PR trend gate (run via the
# tier-2 pytest marker: `pytest -m tier2`).
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:   # `python benchmarks/run.py` from anywhere
    sys.path.insert(0, str(_REPO))

BENCH_JSON = _REPO / "BENCH_codec.json"
DECODE_PATHS = ("decode_fastpath_f32", "decode_segments_f32",
                "decode_ring_f32", "decode_seed_f32")
ENCODE_PATHS = ("encode_vectored_f32", "numpy_ta_f32")
REGRESSION_FACTOR = 2.0


def check(factor: float = REGRESSION_FACTOR,
          out: str | None = None) -> int:
    """Fresh codec bench vs committed BENCH_codec.json.

    Returns 0 when every decode and encode path is within ``factor`` of
    the committed throughput, 1 on a regression (or a missing/malformed
    committed record).  ``out`` writes the fresh record to a file *before*
    comparing — CI uploads it as an artifact whether the gate passes or
    not, without paying for a second bench run.
    """
    from benchmarks import bench_codec_throughput, bench_wire_bytes

    if not BENCH_JSON.exists():
        print(f"check: no committed record at {BENCH_JSON}")
        return 1
    committed = json.loads(BENCH_JSON.read_text())
    _, fresh = bench_codec_throughput.run_json()
    _, wire = bench_wire_bytes.run_json()
    fresh["wire_bytes_per_round"] = wire
    if out:
        Path(out).write_text(json.dumps(fresh, indent=2) + "\n")
        print(f"check: wrote fresh record to {out}")
    failures = {"decode": [], "encode": []}
    compared = 0
    for size, entry in committed.get("sizes", {}).items():
        for kind, names in (("decode", DECODE_PATHS),
                            ("encode", ENCODE_PATHS)):
            for name in names:
                old = entry.get(name, {}).get("MBps")
                new = fresh["sizes"].get(size, {}).get(name, {}).get("MBps")
                if not old or not new:
                    continue
                compared += 1
                if new * factor < old:
                    failures[kind].append(
                        f"  {name} @ {size} params: {old:.1f} -> {new:.1f} "
                        f"MB/s ({old / new:.1f}x slower)")
    if compared == 0:
        print("check: committed record has no comparable codec entries")
        return 1
    failed = False
    # compression acceptance: q8 chunks must stay within the wire-bytes
    # bound of f32 (deterministic — re-measured fresh, no baseline drift)
    for size, entry in wire["sizes"].items():
        ratio = entry["q8"]["ratio_vs_f32"]
        if ratio > bench_wire_bytes.Q8_MAX_RATIO:
            failed = True
            print(f"check: q8 wire bytes @ {size} params = {ratio:.3f}x "
                  f"f32, above the {bench_wire_bytes.Q8_MAX_RATIO}x bound")
    for kind, lines in failures.items():
        if lines:
            failed = True
            print(f"check: {kind} throughput regressed >{factor}x:")
            print("\n".join(lines))
    if failed:
        return 1
    print(f"check: OK ({compared} codec entries within {factor}x "
          "of committed BENCH_codec.json)")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--check", action="store_true",
                        help="compare a fresh codec bench against the "
                             "committed BENCH_codec.json; exit 1 on >2x "
                             "decode-throughput regression")
    parser.add_argument("--factor", type=float, default=REGRESSION_FACTOR,
                        help="regression factor for --check (default "
                             f"{REGRESSION_FACTOR}; CI uses a looser bound "
                             "because the committed baseline was measured "
                             "on different hardware)")
    parser.add_argument("--out", default=None,
                        help="with --check: also write the freshly "
                             "measured record to this path (written before "
                             "the comparison, so a failing gate still "
                             "produces the artifact)")
    args = parser.parse_args()
    if args.check:
        return check(args.factor, args.out)

    from benchmarks import (
        bench_codec_throughput,
        bench_fault_sweep,
        bench_fl_round,
        bench_lenet,
        bench_message_sizes,
        bench_scale,
        bench_wire_bytes,
    )

    def _merge_into_bench_json(update: dict) -> None:
        # BENCH_codec.json carries sections from more than one bench; a
        # re-run of one section must never clobber the committed numbers
        # of another (the codec baseline was measured on dev hardware)
        record = (json.loads(BENCH_JSON.read_text())
                  if BENCH_JSON.exists() else {})
        record.update(update)
        BENCH_JSON.write_text(json.dumps(record, indent=2) + "\n")

    def codec_run():
        rows, record = bench_codec_throughput.run_json()
        _merge_into_bench_json(record)
        rows.append(f"# wrote {BENCH_JSON}")
        return rows

    def fault_sweep_run():
        rows, record = bench_fault_sweep.run_json()
        _merge_into_bench_json({"fault_sweep": record})
        rows.append(f"# merged fault_sweep into {BENCH_JSON}")
        return rows

    def wire_bytes_run():
        rows, record = bench_wire_bytes.run_json()
        _merge_into_bench_json({"wire_bytes_per_round": record})
        rows.append(f"# merged wire_bytes_per_round into {BENCH_JSON}")
        return rows

    def scale_run():
        rows, record = bench_scale.run_json()
        _merge_into_bench_json({"scale_rounds": record})
        rows.append(f"# merged scale_rounds into {BENCH_JSON}")
        return rows

    sections = [
        ("table1_message_sizes", bench_message_sizes.run),
        ("table2_lenet5", bench_lenet.run),
        ("codec_throughput", codec_run),
        ("wire_bytes_per_round", wire_bytes_run),
        ("fl_round_accounting", bench_fl_round.run),
        ("uplink_airtime_shared_medium", bench_fl_round.run_uplink_airtime),
        ("fault_sweep", fault_sweep_run),
        ("scale_rounds", scale_run),
    ]
    for name, fn in sections:
        t0 = time.time()
        rows = fn()
        dt = time.time() - t0
        print(f"## {name} ({dt:.1f}s)")
        print("\n".join(rows))
        print()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
