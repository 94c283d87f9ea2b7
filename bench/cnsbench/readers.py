"""Shared arithmetic of the per-layer metric readers (``metrics/``).

A reader gets the traced run's context: ``job`` (the traced
``JobOut``), ``reduction`` (``trace.Reduction``), ``ticks`` (ticks of
the job) and ``n_devices``.  It returns a number, or ``None`` where the
trace holds nothing for it; the harness then leaves the metric out.
"""
from __future__ import annotations


def phase_ms_per_tick(ctx: dict, phase: str):
    """Device op time of one tick phase, in ms per tick."""
    s = ctx["reduction"].phase_s.get(phase)
    if not s:
        return None
    return s * 1e3 / ctx["ticks"]
