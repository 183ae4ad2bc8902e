"""All pods bound in the window over all wall time of the window: from the
first arrival of the first measured wave (or cycle) to the last solve
response held.  Arrival apply, the solve requests and their responses are
inside; the checks of the placements are not.  Never a median of rounds."""


def read(ctx):
    return ctx.books.bound_in_window / (ctx.t_close - ctx.t_open)
