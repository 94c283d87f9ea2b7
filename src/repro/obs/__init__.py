"""Opt-in observability for the tensor DES (DESIGN.md §9).

Coordinated pieces; all but ``hostspans`` (host-side only, nothing in
the traced program) are default-off and bit-identical when off:

- :mod:`.telemetry` — device-side metric-row ring + sampled span ring,
  double-buffered io_callback flush (the paper's Exporter, §3.1).
- :mod:`.export` — host-side exporter registry rendering OTel /
  Prometheus-style rows live during runs.
- :mod:`.spans` — host-side trace-tree reconstruction for the seeded
  1-in-k request sample, cross-checked against the tropical-closure
  critical path (paper §4.3.2).
- :mod:`.hostspans` — named host spans of each ``Simulation.run`` /
  ``run_batch`` stage (on the profiler's clock when it traces) and the
  backend-compile and compile-cache counts behind ``Simulation.stats()``.
- :mod:`.slo` — per-service SLO objectives, multi-window burn-rate
  alerting, and the alert state machine feeding the control plane
  (DESIGN.md §10).

Submodules import lazily: most of them import ``core``, whose engine
imports ``obs.hostspans``, so an eager package import would cycle.
"""
from __future__ import annotations

import importlib

_SUBMODULES = ("telemetry", "export", "spans", "hostspans", "slo")

__all__ = list(_SUBMODULES)


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
