"""scheduler, device half: flight record ``solve_device_s`` (host blocked
on jitted solve results), mean per round of the window."""


def read(ctx):
    if not ctx.rounds:
        return None
    return sum(r["solve_device_s"] for r in ctx.rounds) * 1e3 / len(ctx.rounds)
