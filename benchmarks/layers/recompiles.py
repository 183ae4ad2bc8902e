"""scheduler, device half: ``solver_recompiles_total`` after the window
minus before it.  Reads 0 in a sound run (0 is a count here, not a share)."""


def read(ctx):
    return float(ctx.recompiles)
