"""entry: wall of the warm-up's first round in set-up (it compiles, or loads
from the persistent cache, every program of the shape)."""


def read(ctx):
    return ctx.first_round_s
