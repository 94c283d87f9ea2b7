"""Host time of the traced job's ``sim/init_state`` stage inside
``Simulation.run``, ms (the engine's own span)."""
from cnsbench.engine_spans import host_ms


def read(ctx):
    return host_ms(ctx, "init_state")
