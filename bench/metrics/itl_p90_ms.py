"""90th percentile inter-token gap (ms): every gap between two consecutive
tokens of one request, both returned inside the window (host clock)."""

import numpy as np


def read(ctx):
    w = ctx.window
    gaps = []
    for times in ctx.rec.times.values():
        t = np.asarray(times)
        t = t[(t > w["t0"]) & (t <= w["t_end"])]
        gaps.extend(np.diff(t).tolist())
    return float(np.percentile(gaps, 90)) * 1e3 if gaps else None
