"""The engine's own record of the traced job, for the metric readers.

The program times each host stage of a ``Simulation.run`` call
(``repro/obs/hostspans.py``) and counts its compiles
(``Simulation.stats()``).  The traced job is the last engine call
before the readers run; its record is found by the job's seed.  A
program that keeps no such record gives ``None``, and the harness then
leaves the metric out.
"""
from __future__ import annotations


def traced_record(ctx: dict):
    """The engine's span record of the traced job, or ``None``."""
    try:
        from repro.obs import hostspans
    except ImportError:
        return None
    rec = hostspans.last()
    if rec is None or rec.ids.get("seed") != ctx["job"].seed:
        return None
    return rec


def host_ms(ctx: dict, stage: str):
    """Host time of one stage (``sim/<stage>``) of the traced job, ms."""
    rec = traced_record(ctx)
    if rec is None or f"sim/{stage}" not in rec.seconds:
        return None
    return rec.seconds[f"sim/{stage}"] * 1e3


def setup_stats(ctx: dict):
    """The engine's counters as set-up left them: the counters now, less
    what the traced job added.  ``compile_s`` is left out if the traced
    job compiled a program (its seconds are not kept apart)."""
    rec = traced_record(ctx)
    if rec is None:
        return None
    from repro.core import Simulation

    stats = Simulation.stats()
    stats["backend_compiles"] -= sum(rec.compiles.values())
    stats["persistent_cache_hits"] -= sum(rec.cache_hits.values())
    if "sim/compile" in rec.compiles:
        del stats["compile_s"]
    return stats
