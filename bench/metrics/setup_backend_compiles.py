"""Backend compiles (XLA compilations or persistent-cache loads) during
set-up inside the engine's calls: the run program and each eager op's
first use.  The engine's ``backend_compiles`` counter."""
from cnsbench.engine_spans import setup_stats


def read(ctx):
    stats = setup_stats(ctx)
    return None if stats is None else stats["backend_compiles"]
