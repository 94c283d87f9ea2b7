"""Device time of the engine's Derive tick phase over the calls the
traced job spawned (its readback's ``spawned``, summed over requests and
sweep points), us per call."""
import numpy as np


def read(ctx):
    s = ctx["reduction"].phase_s.get("Derive")
    outs = ctx["job"].outputs
    if not s or not all("spawned" in o for o in outs):
        return None
    calls = sum(int(np.asarray(o["spawned"]).sum()) for o in outs)
    return s * 1e6 / calls if calls else None
