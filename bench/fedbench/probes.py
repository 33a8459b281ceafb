"""What the benchmark observes of the program from outside it.

* ``CompileMonitor``: backend compiles (which include persistent-cache
  loads) and cache hits and writes, from ``jax.monitoring`` events.
* ``Spans``: host spans around calls into each layer, made by wrapping
  methods on the objects the benchmark built.  Each span is kept in memory
  and also written into the profiler's trace as a ``TraceAnnotation``, so
  the trace reduction can name what the host did during a device gap.
* ``Recorder``: what the checked job's rounds produced, for the comparison
  with the reference: each client's reported losses, which clients built
  an uplink, each folded update and its weight, and each round's global.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

# span names, by layer; the metric readers find them by these names
ROUND = "fl.round"
DISSEMINATE = "fl.disseminate"
TRAIN = "fl.train"
ENCODE = "fl.encode"
FOLD = "fl.fold"


class CompileMonitor:
    """Backend compile seconds and persistent-cache hits/writes, from
    JAX's monitoring events.  JAX times a persistent-cache load as a
    backend compile too, so ``compiles`` counts every executable the
    process had to obtain."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1     # JAX records a miss as it writes

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


@dataclass
class Span:
    name: str
    t0: float       # host perf_counter seconds
    t1: float
    count: int = 0  # work the call reports (trained samples for TRAIN)


@dataclass
class Spans:
    spans: list[Span] = field(default_factory=list)

    def wrap(self, obj, method: str, name: str, count=None) -> None:
        inner = getattr(obj, method)
        spans = self.spans

        def timed(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                t0 = time.perf_counter()
                out = inner(*args, **kwargs)
                spans.append(Span(name, t0, time.perf_counter(),
                                  count(out) if count else 0))
                return out

        setattr(obj, method, timed)

    def instrument_clients(self, clients) -> None:
        for c in clients:
            self.wrap(c, "train_locally", TRAIN,
                      count=lambda upd: int(upd.dataset_size))
            self.wrap(c, "local_model_chunks", ENCODE)

    def instrument_job(self, sim) -> None:
        self.wrap(sim, "run_round", ROUND)
        self.wrap(sim, "_disseminate", DISSEMINATE)
        self.wrap(sim.server, "accumulate_update", FOLD)

    def total(self, name: str, t0: float, t1: float) -> float:
        """Seconds of ``name`` spans that start inside [t0, t1)."""
        return sum(s.t1 - s.t0 for s in self.spans
                   if s.name == name and t0 <= s.t0 < t1)

    def counted(self, name: str, t0: float, t1: float) -> int:
        return sum(s.count for s in self.spans
                   if s.name == name and t0 <= s.t0 < t1)


def unwrap(obj, *methods: str) -> None:
    """Drop instance-level wrappers, restoring the class's methods."""
    for m in methods:
        obj.__dict__.pop(m, None)


@dataclass
class RoundRecord:
    losses: dict[int, tuple[float, float]] = field(default_factory=dict)
    uploaded: list[int] = field(default_factory=list)
    folded: dict[int, tuple[np.ndarray, int]] = field(default_factory=dict)
    global_after: np.ndarray | None = None
    installed: bool = False
    reporters: list[int] = field(default_factory=list)


class Recorder:
    """Copies of what the checked job's rounds produced."""

    def __init__(self) -> None:
        self.rounds: list[RoundRecord] = []
        self._cur: RoundRecord | None = None

    def instrument_clients(self, clients) -> None:
        for c in clients:
            self._wrap_client(c)

    def _wrap_client(self, c) -> None:
        train, chunks = c.train_locally, c.local_model_chunks

        def train_locally():
            upd = train()
            self._cur.losses[c.client_id] = (float(upd.metadata.train_loss),
                                             float(upd.metadata.val_loss))
            return upd

        def local_model_chunks(*args, **kwargs):
            self._cur.uploaded.append(c.client_id)
            return chunks(*args, **kwargs)

        c.train_locally = train_locally
        c.local_model_chunks = local_model_chunks

    def instrument_job(self, sim) -> None:
        server = sim.server
        accumulate = server.accumulate_update

        def accumulate_update(client_id, params, dataset_size):
            self._cur.folded[client_id] = (np.array(params, np.float32),
                                           int(dataset_size))
            accumulate(client_id, params, dataset_size)

        server.accumulate_update = accumulate_update
        run_round = sim.run_round

        def recorded_round():
            self._cur = RoundRecord()
            result = run_round()
            self._cur.global_after = np.array(server.global_params,
                                              np.float32)
            self._cur.installed = installed(result)
            self._cur.reporters = list(result.reporters)
            self.rounds.append(self._cur)
            return result

        sim.run_round = recorded_round


def installed(result) -> bool:
    """Did this round install a new global model?"""
    return bool(result.quorum_met and result.reporters)
