"""Output tokens per second: every token the window produced, seen at the
step end that returned it, over the window's length (host clock)."""

import numpy as np


def read(ctx):
    w = ctx.window
    n = sum(int(np.count_nonzero((np.asarray(t) > w["t0"])
                                 & (np.asarray(t) <= w["t_end"])))
            for t in ctx.rec.times.values())
    return n / w["seconds"]
