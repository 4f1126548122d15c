"""90th percentile time to first token (ms), over every request due in the
window: first token's step end minus the request's due time. A request
with no first token by the end of the drain counts with the drain's end."""

import numpy as np


def read(ctx):
    if not ctx.window_ids:
        return None
    end = ctx.window["drain_end"]
    vals = []
    for rid in ctx.window_ids:
        times = ctx.rec.times.get(rid)
        vals.append((times[0] if times else end) - ctx.due[rid])
    return float(np.percentile(vals, 90)) * 1e3
