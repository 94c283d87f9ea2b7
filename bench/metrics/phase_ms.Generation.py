"""Device time of the engine's Generation tick phase, ms per tick."""
from cnsbench.readers import phase_ms_per_tick


def read(ctx):
    return phase_ms_per_tick(ctx, "Generation")
