"""Share of the traced job's window in which no operation ran on the
device: 1 - (union of device op intervals) / window."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0 or red.n_ops == 0:
        return None
    return red.idle_share
