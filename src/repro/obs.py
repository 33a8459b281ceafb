"""Spans and counters of the federated round, kept in memory per round.

The program's own record of where a round's host time goes and what it
moves between host and device:

* ``span(name)`` is a context manager.  It times the block on
  ``time.perf_counter`` and keeps its total time and its self time (the
  total minus what its child spans cover).  It also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the span lands in
  a profiler trace on the device trace's clock; that copy costs only while
  a profiler session is active.
* ``count(name, n)`` adds ``n`` to a counter; ``h2d`` and ``d2h`` count
  host-to-device and device-to-host copies and their bytes.
* The closing ``ROUND`` span rolls everything recorded inside it (per
  span name: self seconds, total seconds, calls, compiles; per counter:
  its sum) into one round record, kept in a history of the last
  ``HISTORY`` rounds.  Work outside any round (set-up, warm-up) goes to a
  process-total record instead.  ``window(t0, t1)`` sums the records of
  the rounds that started in ``[t0, t1)``.
* Backend compiles, their seconds and persistent-cache hits and writes
  are counted under the innermost open span, from ``jax.monitoring``
  events, so a record says which step compiled.

The clock lives in this module only: ``fl/`` and ``transport/`` read no
wall time (the ``det`` lint rule), and ``span`` returns nothing to its
caller, so no round decision can depend on a reading.  Counter arithmetic
lives here too.  The simulation is single-threaded, so one stack of open
spans serves it.
"""
from __future__ import annotations

import time
from collections import deque

import jax

# span names, one per layer boundary; every name starts with "fl." so a
# profiler trace's reduction names device idle time by them
ROUND = "fl.engine.round"            # RoundEngine.run / resume
REPORT = "fl.engine.report"          # a progress report: send and decode
FINALIZE = "fl.engine.finalize"      # finalize or abort, finish_round
SERVER_ENCODE = "fl.server.encode"   # the global's chunk stream
SCHED_DOWNLINK = "fl.sched.downlink"  # dissemination on the link or medium
SCHED_UPLINK = "fl.sched.uplink"     # upload sessions and their scheduler
ASSEMBLE = "fl.assemble"             # decode and install a completed ring
CLIENT_TRAIN = "fl.client.train"     # FLClient.train_locally
CLIENT_ENCODE = "fl.client.encode"   # FLClient.local_model_chunks
SERVER_FOLD = "fl.server.fold"       # one fold into the running FedAvg
WAIT = "fl.wait"                     # the host blocked on device values

HISTORY = 1024                       # round records kept
NO_SPAN = "none"                     # compiles outside every span

# per span name: [self_s, total_s, calls, compiles, compile_s,
#                 cache_hits, cache_writes]
_FIELDS = ("self_s", "total_s", "calls", "compiles", "compile_s",
           "cache_hits", "cache_writes")

clock = time.perf_counter


class _Record:
    __slots__ = ("t0", "t1", "spans", "counters")

    def __init__(self, t0: float | None) -> None:
        self.t0 = t0
        self.t1: float | None = None
        self.spans: dict[str, list] = {}
        self.counters: dict[str, int] = {}

    def entry(self, name: str) -> list:
        e = self.spans.get(name)
        if e is None:
            e = self.spans[name] = [0.0, 0.0, 0, 0, 0.0, 0, 0]
        return e

    def as_dict(self) -> dict:
        return {"t0": self.t0, "t1": self.t1,
                "spans": {n: dict(zip(_FIELDS, e))
                          for n, e in self.spans.items()},
                "counters": dict(self.counters)}


_stack: list["_Span"] = []            # open spans, innermost last
_rounds: list[_Record] = []           # open round records, innermost last
_history: deque[_Record] = deque(maxlen=HISTORY)
_process = _Record(None)
_compiles = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
             "cache_writes": 0}


def _current() -> _Record:
    return _rounds[-1] if _rounds else _process


class _Span:
    __slots__ = ("name", "_t0", "_child", "_note")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> None:
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._child = 0.0
        self._t0 = clock()
        _stack.append(self)
        if self.name == ROUND:
            _rounds.append(_Record(self._t0))

    def __exit__(self, *exc) -> None:
        t1 = clock()
        _stack.pop()
        d = t1 - self._t0
        if _stack:
            _stack[-1]._child += d
        rec = _current()
        e = rec.entry(self.name)
        e[0] += d - self._child
        e[1] += d
        e[2] += 1
        if self.name == ROUND:
            rec.t1 = t1
            _history.append(_rounds.pop())
        self._note.__exit__(*exc)


def span(name: str) -> _Span:
    """``with span(name): ...`` records the block under ``name``."""
    return _Span(name)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the innermost open round."""
    c = _current().counters
    c[name] = c.get(name, 0) + n


def h2d(arrays) -> None:
    """Count the host arrays among ``arrays`` as copies to the device."""
    host = [a for a in arrays if not isinstance(a, jax.Array)]
    count("h2d_bytes", sum(a.nbytes for a in host))
    count("h2d_transfers", len(host))


def d2h(arrays) -> None:
    """Count the device arrays among ``arrays`` as copies to the host."""
    dev = [a for a in arrays if isinstance(a, jax.Array)]
    count("d2h_bytes", sum(a.nbytes for a in dev))
    count("d2h_transfers", len(dev))


def history() -> list[dict]:
    """The kept round records, oldest first: ``t0``/``t1`` (the round
    span's bounds), ``spans`` (name -> self_s, total_s, calls, compiles,
    compile_s, cache_hits, cache_writes) and ``counters``."""
    return [r.as_dict() for r in _history]


def process() -> dict:
    """What was recorded outside every round."""
    return _process.as_dict()


def window(t0: float, t1: float) -> dict:
    """The sum of the round records whose round started in ``[t0, t1)``:
    ``rounds``, ``spans`` and ``counters`` as in ``history()``."""
    spans: dict[str, list] = {}
    counters: dict[str, int] = {}
    rounds = 0
    for r in _history:
        if not t0 <= r.t0 < t1:
            continue
        rounds += 1
        for name, e in r.spans.items():
            acc = spans.setdefault(name, [0.0, 0.0, 0, 0, 0.0, 0, 0])
            for i, v in enumerate(e):
                acc[i] += v
        for name, v in r.counters.items():
            counters[name] = counters.get(name, 0) + v
    return {"rounds": rounds,
            "spans": {n: dict(zip(_FIELDS, e)) for n, e in spans.items()},
            "counters": counters}


def compile_totals() -> dict:
    """Compiles, compile seconds, cache hits and cache writes since this
    module was imported, over the whole process."""
    return dict(_compiles)


def reset() -> None:
    """Forget every record and total (for tests; no span may be open)."""
    global _process
    if _stack:
        raise RuntimeError("reset() with open spans")
    _history.clear()
    _process = _Record(None)
    for k in _compiles:
        _compiles[k] = 0


# -- compile counting ---------------------------------------------------------

def _compile_entry() -> list:
    return _current().entry(_stack[-1].name if _stack else NO_SPAN)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        e = _compile_entry()
        e[3] += 1
        e[4] += duration
        _compiles["compiles"] += 1
        _compiles["compile_s"] += duration


def _on_event(event: str, **_) -> None:
    # JAX records a persistent-cache miss as it writes the entry
    if event == "/jax/compilation_cache/cache_hits":
        _compile_entry()[5] += 1
        _compiles["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile_entry()[6] += 1
        _compiles["cache_writes"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
