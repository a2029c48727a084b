"""Session: median wall time of the window's decode steps (the program's
``decode`` events, dispatch to the tokens on the host)."""
import numpy as np


def read(ctx):
    if not ctx.decode:
        return None
    return float(np.median([e - s for s, e, _ in ctx.decode])) * 1e3
