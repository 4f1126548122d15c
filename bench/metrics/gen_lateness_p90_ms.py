"""90th percentile of how late the load generator handed a request to the
engine after its due time (ms, host clock); open loops only."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.late, 90)) * 1e3 if ctx.late else None
