"""The whole step's share (%) of the chip's bf16 peak: model FLOPs of the
tokens the window processed (2 per weight per token, attention at each
row's live context, one unembedding per emitted token; bench/flops.py)
over the window's seconds times the peak."""

from bench import flops


def read(ctx):
    if not ctx.rec.steps:
        return None
    total = sum(flops.model_flops(ctx.model, rows, emitted)
                for rows, emitted in ctx.rec.steps)
    return 100.0 * total / (ctx.window["seconds"]
                            * ctx.peak["bf16_flops_per_s"])
