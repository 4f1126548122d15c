"""Share (%) of the LAMP kernels' device time spent in the look-ahead pass:
device seconds of the kernel ops (`bench/kernels.py`) whose text holds
`"lamp_pass":"lookahead"` over those of both passes (the selective-recompute
pass is tagged `"recompute"`; kernels/paged_attention.py). A program whose
kernels carry no pass tag reads nothing."""

from bench.engine_trace import pass_seconds


def read(ctx):
    if not ctx.red:
        return None
    s = pass_seconds(ctx.red)
    both = s["lookahead"] + s["recompute"]
    return 100.0 * s["lookahead"] / both if both else None
