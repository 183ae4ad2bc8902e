"""Process start to window open: imports, assembly, state load, the warm-up
wave or settle cycles, compile-cache loads (or, cold, compilation)."""


def read(ctx):
    return ctx.t_open - ctx.t_start
