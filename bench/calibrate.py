#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for setting a cell's limits.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--faults unchanged,half,altered] [--fault-seeds 3] [--out FILE]

For each seed, in this one process: the cell's deployment at its own size,
the checked job through ``FLSimulation.run_round`` (what every benchmark
run compares), the reference, and the comparison; then the control (the
reference in bfloat16, put in the program's place) on the same seed; then,
on the first ``--fault-seeds`` seeds, the checked job again with each fault
of ``fedbench.faults`` planted underneath.  No window is measured.  One
JSON line per reading goes to stdout and to ``--out``.

The benchmark's own runs never run this.  Like ``run.py`` it needs a TPU,
and keeps JAX's compilation cache in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings_for(cell, seed: int, faults=(), control: bool = True):
    """Yield (kind, readings) for one seed: "program", then "control",
    then each fault."""
    import jax.numpy as jnp

    from fedbench import check
    from fedbench import faults as planted_faults
    from fedbench.deploy import Deployment
    from fedbench.harness import checked_job
    from fedbench.reference import JobReference

    dep = Deployment(cell, seed)
    model = dep.model
    reference = JobReference(dep)
    g0, record = checked_job(dep)
    ref = reference.run(g0, record)
    yield "program", check.readings(model, cell.traffic, g0, record, ref)
    if control:
        ctrl = JobReference(dep, jnp.bfloat16).run(g0, record)
        yield "control", check.readings(
            model, cell.traffic, g0,
            planted_faults.control_record(ctrl, record), ref)
    for fault in faults:
        with planted_faults.planted(fault):
            g0, rec = checked_job(dep)
        yield fault, check.readings(model, cell.traffic, g0, rec,
                                    reference.run(g0, rec))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    import jax

    from fedbench import spec
    from repro import compile_cache

    compile_cache.enable(ROOT)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate.py: JAX found no TPU ({dev.platform!r})",
              file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload, ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            for kind, values in readings_for(
                    cell, seed, faults if i < args.fault_seeds else ()):
                line = json.dumps({"workload": args.workload, "seed": seed,
                                   "kind": kind, **values,
                                   "elapsed_s": time.perf_counter() - t0})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
