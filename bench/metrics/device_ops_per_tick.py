"""Device operations per simulated tick in the traced job (per chip)."""


def read(ctx):
    red = ctx["reduction"]
    if red.n_ops == 0:
        return None
    return red.n_ops / ctx["n_devices"] / ctx["ticks"]
