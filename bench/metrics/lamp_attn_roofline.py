"""The LAMP attention kernels' share (%) of their roofline: the least time
the attention of every step needs (per layer, the larger of its FLOPs over
the bf16 peak and its bytes over HBM bandwidth; bench/flops.py) over the
device time of the two kernels in the trace. The window's recomputed
products are spread over its steps in proportion to their causal spans."""

from bench import flops
from bench.kernels import kernel_seconds


def read(ctx):
    if not ctx.red or not ctx.rec.steps:
        return None
    k = kernel_seconds(ctx.red)
    if not k:
        return None
    m = flops.dims(ctx.model)
    spans = [sum(flops.keys_seen(s, q) for s, q in rows) * m["H"]
             for rows, _ in ctx.rec.steps]
    total = sum(spans) or 1
    itemsize = 2 if ctx.config["engine"].get("kv_dtype") == "bfloat16" else 4
    least = 0.0
    for (rows, _), span in zip(ctx.rec.steps, spans):
        sel = ctx.delta["selected"] * span / total / m["L"]
        f, b = flops.attn_cost(ctx.model, rows, kv_itemsize=itemsize,
                               selected=sel)
        least += m["L"] * flops.least_time(f, b, ctx.peak)
    return 100.0 * least / k
