"""One run of one cell: set-up, the checked job, the measured window, the
optional trace, the comparison with the reference, and the result line.

The window is whole jobs: it opens at a job boundary after set-up and
closes at the end of the first job that ends at or after ``seconds``, and
never before the traffic's ``tally_jobs`` jobs have ended.  ``round_s`` and
``updates_per_s`` are all the work over all the time of whole fresh
federations.  The result line's ``attempted`` and ``failed`` count the
window's first ``tally_jobs`` jobs only: job ``j`` draws its selection,
dropouts and medium from ``(seed, j)``, so at one seed those are the same
jobs at any speed, and a faster program is not judged on the seeded
failures of the extra jobs it fits in.  The ``window`` note keeps the
whole window's counts as ``attempted_window`` and ``failed_window``.
"""
from __future__ import annotations

import gc
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import jax

from fedbench import check, probes, spec, trace
from fedbench.deploy import Deployment
from fedbench.reference import JobReference

PEAKS = spec.BENCH_DIR / "peaks.json"


def emit(**fields) -> None:
    """An informational line on stdout (never the last one)."""
    print(json.dumps(fields), flush=True)


@dataclass
class Window:
    t0: float
    t1: float
    results: list       # RoundResult per round, in order
    job_ends: list      # len(results) at the end of each job

    @property
    def jobs(self) -> int:
        return len(self.job_ends)


def run_window(dep: Deployment, seconds: float, first_job: int,
               before_job=None) -> Window:
    rounds = dep.traffic["rounds_per_job"]
    tally = dep.traffic["tally_jobs"]
    results, job_ends, job = [], [], first_job
    t0 = time.perf_counter()
    while True:
        sim = dep.new_job(job)
        if before_job:
            before_job(sim)
        for _ in range(rounds):
            results.append(sim.run_round())
        job += 1
        job_ends.append(len(results))
        if (len(job_ends) >= tally
                and time.perf_counter() - t0 >= seconds):
            break
    return Window(t0, time.perf_counter(), results, job_ends)


def _attempted_folded(results: list) -> tuple[int, int]:
    return (sum(len(r.participants) for r in results),
            sum(len(r.reporters) for r in results if probes.installed(r)))


def window_stats(win: Window, tally_jobs: int) -> dict:
    """End-to-end numbers over a window's rounds and its wall time;
    ``attempted`` and ``failed`` over its first ``tally_jobs`` jobs."""
    results, wall_s = win.results, win.t1 - win.t0
    attempted, folded = _attempted_folded(results)
    tally_attempted, tally_folded = _attempted_folded(
        results[:win.job_ends[tally_jobs - 1]])
    return {
        "rounds": len(results),
        "round_s": wall_s / len(results),
        "updates_per_s": folded / wall_s,
        "attempted": tally_attempted,
        "failed": tally_attempted - tally_folded,
        "attempted_window": attempted,
        "failed_window": attempted - folded,
        "folded": folded,
        "missed_quorum": sum(not probes.installed(r) for r in results),
        "dropped": sum(len(r.dropped) for r in results),
        "stragglers": sum(len(r.stragglers) for r in results),
        "virtual_airtime_s": sum(r.clock_s for r in results),
    }


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_flops(kind: str) -> float:
    peaks = json.loads(PEAKS.read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return float(peaks[kind]["bf16_flops_per_s"])


def memory_peak() -> int | None:
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(peaks) if peaks else None


def checked_job(dep: Deployment) -> tuple:
    """Job 0 through the window's own call, recorded for the comparison
    with the reference."""
    rec = probes.Recorder()
    rec.instrument_clients(dep.clients)
    g0 = dep.initial_global(0)
    sim = dep.new_job(0, g0)
    rec.instrument_job(sim)
    for _ in range(dep.traffic["rounds_per_job"]):
        sim.run_round()
    for c in dep.clients:
        probes.unwrap(c, "train_locally", "local_model_chunks")
    return g0, rec.rounds


def trace_first_round(sim, log_dir: str, done: dict) -> None:
    """Take a profiler trace of ``sim``'s next round only."""
    inner = sim.run_round

    def traced_round():
        sim.run_round = inner
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations, not every dispatch
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            return inner()
        finally:
            jax.profiler.stop_trace()
            done["t1"] = time.perf_counter()

    sim.run_round = traced_round


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             monitor: probes.CompileMonitor, t_start: float) -> dict:
    model = spec.load_reference(cell.config["model"])
    dep = Deployment(cell.config, cell.traffic, seed, model)
    g0, record = checked_job(dep)
    dep.warm_clients(skip={c for r in record for c in r.losses})
    set_up = monitor.snapshot()

    spans = probes.Spans()
    traced_round: dict = {}
    log_dir = tempfile.TemporaryDirectory(prefix="fedbench-trace-")

    def before_job(sim):
        if traced:
            spans.instrument_job(sim)
            if not traced_round:
                traced_round["t1"] = None
                trace_first_round(sim, log_dir.name, traced_round)

    if traced:
        spans.instrument_clients(dep.clients)
    setup_s = time.perf_counter() - t_start
    win = run_window(dep, seconds, 1, before_job)
    wall = win.t1 - win.t0
    stats = window_stats(win, cell.traffic["tally_jobs"])
    in_window = monitor.snapshot()
    emit(phase="set_up", setup_s=setup_s, **set_up)
    emit(phase="window", wall_s=wall, jobs=win.jobs,
         tally_jobs=cell.traffic["tally_jobs"],
         compiles_in_window=in_window["compiles"] - set_up["compiles"],
         cache_writes_in_window=(in_window["cache_writes"]
                                 - set_up["cache_writes"]), **stats)

    device = device_info()
    device["memory_peak_bytes"] = memory_peak()
    metrics, summary = {}, None
    if traced:
        xplanes = sorted(Path(log_dir.name).rglob("*.xplane.pb"))
        events = trace.load_events(str(xplanes[-1]))
        summary = trace.reduce(events,
                               *trace.first_span(events, probes.ROUND))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        # layer times over the window's rounds after the traced one
        ctx = {"spans": spans, "t0": traced_round["t1"], "t1": win.t1,
               "rounds": stats["rounds"] - 1, "trace": summary,
               "train_flops_per_sample": model.train_flops_per_sample(),
               "peak_flops_per_s": peak_flops(device["kind"]) * len(
                   jax.devices())}
        for m in cell.per_layer:
            value = spec.load_metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"round_s": stats["round_s"],
               "updates_per_s": stats["updates_per_s"], "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    log_dir.cleanup()

    # the program's state is freed before the reference runs
    plans, images, labels = dep.plans, dep.images, dep.labels
    del dep, win
    gc.collect()
    t_ref = time.perf_counter()
    ref = JobReference(model, cell.config, cell.traffic, seed, plans, images,
                       labels).run(g0, record)
    values = check.readings(model, cell.traffic, g0, record, ref)
    correct, shown = check.judge(values, cell.limits)
    emit(phase="check", reference_s=time.perf_counter() - t_ref)

    out = {"correct": correct, "attempted": stats["attempted"],
           "failed": stats["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = shown
    return out


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on stderr,
    then the result as the last line on stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
