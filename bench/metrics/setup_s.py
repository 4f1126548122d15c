"""Set-up seconds: from the command's start to the window's opening --
imports, device start, weights, the engine and its pool, the warm-up of
every bucket the window can reach (compiles included when the cache is
cold), and a closed loop's first prefills (host clock)."""


def read(ctx):
    return ctx.setup_s
