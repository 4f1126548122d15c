"""Share (%) of device-busy time spent in the two LAMP attention kernels."""

from bench.kernels import kernel_seconds


def read(ctx):
    if not ctx.red or not ctx.red["busy_s"]:
        return None
    k = kernel_seconds(ctx.red)
    return 100.0 * k / ctx.red["busy_s"] if k else None
