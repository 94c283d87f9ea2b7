"""Conditional calls the traced job made, per tick: the engine's
``branch_taken`` counter, which ``Simulation.run`` copies onto the job's
span record where the graph has calls made with 0 < p < 1."""
from cnsbench.engine_spans import traced_record


def read(ctx):
    counts = getattr(traced_record(ctx), "counts", None) or {}
    if "branch_taken" not in counts:
        return None
    return counts["branch_taken"] / ctx["ticks"]
