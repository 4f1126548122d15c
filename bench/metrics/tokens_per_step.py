"""Tokens generated per engine step in the window (the scheduler's batch
occupancy): the engine's generated-token counter over its mixed-step
counter, both as deltas over the window."""


def read(ctx):
    d = ctx.delta
    return d["generated"] / d["mixed_steps"] if d["mixed_steps"] else None
