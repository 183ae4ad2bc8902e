"""solve kernels: the rows the batch solve's plane stages granted on in
their Reserve loops (the flight record's ``accept_rows``: trips x the block
of pods a trip, summed over the propose/accept rounds and gang passes of the
round's solves), mean over the window's rounds.  Reads ``None`` on a program
whose flight record keeps no such field."""


def read(ctx):
    rows = [r.get("accept_rows") for r in ctx.rounds]
    if not rows or None in rows:
        return None
    return sum(rows) / len(rows)
