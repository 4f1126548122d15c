"""Host milliseconds per engine step in the engine's own phase spans:
schedule (block allocation runs inside it), emit and defrag, as deltas of
`stats()["phase"]` over the window, per step."""

PHASES = ("schedule", "emit", "defrag")


def read(ctx):
    d = ctx.delta
    if not d["steps"]:
        return None
    us = sum(d["phase_us"].get(p, 0.0) for p in PHASES)
    return us / d["steps"] / 1e3
