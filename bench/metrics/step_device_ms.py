"""Device-busy milliseconds per engine step: the union of device op
intervals in the traced window over the steps the window ran."""


def read(ctx):
    if not ctx.red or not ctx.window["steps"]:
        return None
    return ctx.red["busy_s"] / ctx.window["steps"] * 1e3
