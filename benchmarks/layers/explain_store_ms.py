"""scheduler, host half: busy time of the explanation store's spans
(``bind.explain`` around ``delete``, ``diagnose.explain`` around ``record``)
inside the window, mean per round."""

from benchmarks import program_spans


def read(ctx):
    busy, calls = program_spans.total(
        program_spans.records(ctx),
        lambda r: r["name"] in ("bind.explain", "diagnose.explain"))
    if not calls or not ctx.rounds:
        return None
    return busy * 1e3 / len(ctx.rounds)
