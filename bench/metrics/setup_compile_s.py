"""Seconds set-up spent building the engine's programs (lower + compile,
or a persistent-cache load): the engine's ``compile_s`` counter."""
from cnsbench.engine_spans import setup_stats


def read(ctx):
    stats = setup_stats(ctx)
    return None if stats is None else stats.get("compile_s")
