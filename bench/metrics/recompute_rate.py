"""Share (%) of the KQ products LAMP recomputed in FP32 in the window: the
engine's `lamp_kq_products_total` selected over valid, as deltas."""


def read(ctx):
    d = ctx.delta
    return 100.0 * d["selected"] / d["valid"] if d["valid"] else None
