"""Mean share (%) of the KV pool's blocks in use, sampled after every step
of the window (`PagedKVPool.utilization`)."""

import numpy as np


def read(ctx):
    return 100.0 * float(np.mean(ctx.rec.util)) if ctx.rec.util else None
