#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip and starts no child.  It builds the
cell's federated deployment from the seed (``bench/configs``,
``bench/traffic``), runs every client's local training once and a whole
checked job (the set-up), then measures whole jobs of
``FLSimulation.run_round`` for at least ``--seconds`` and at least the
traffic's ``tally_jobs`` jobs.  With ``--trace 1`` it wraps the layers in
host spans, takes a profiler trace of the window's first job, and reports
the per-layer metrics of ``BENCHMARK.json`` instead of the end-to-end ones.
After the window it compares the checked job with the plain reference
(``bench/fedbench/reference.py``).

Earlier stdout lines are JSON notes (set-up compiles, compiles inside the
window, jobs, rounds, reporters, stragglers, dropouts, virtual airtime,
and the whole window's ``attempted_window`` and ``failed_window``).  The
last stdout line is the result: ``correct``, ``attempted`` and ``failed``
(client updates selected, and those not folded into an installed global,
over the window's first ``tally_jobs`` jobs: the same jobs at every
speed), ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and
last ``checks``, each compared number beside its limit; the same numbers
are the last lines on stderr.  The exit code is non-zero, with no result,
when JAX finds no TPU, fewer chips than the cell asks for, or no program
sources beside ``bench/``.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    import jax

    from fedbench import harness, probes, spec
    from repro import compile_cache

    compile_cache.enable(ROOT)
    monitor = probes.CompileMonitor()
    cell = spec.load_cell(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), monitor, T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
