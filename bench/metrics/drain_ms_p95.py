"""Shadow drain: the 95th percentile of the program's own
``drain_seconds`` histogram over the drains of the window, in ms (a host
clock around a drain whose tier calls block on their results)."""
import numpy as np


def read(ctx):
    if not ctx.drain_s:
        return None
    return 1e3 * float(np.percentile(ctx.drain_s, 95))
