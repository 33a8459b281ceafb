"""The on-chip benchmark's harness: cells from ``BENCHMARK.json``, the
federated deployments they run, the measured window, spans, the trace
reduction, and the plain reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own under ``bench/`` and is found by name
(``fedbench.spec``); nothing here names a cell.
"""
