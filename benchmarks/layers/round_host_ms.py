"""scheduler, host half: flight record, sum of the round's monitor phases
minus the time blocked on the device, mean over the window's rounds."""


def read(ctx):
    if not ctx.rounds:
        return None
    host = [sum(r["phase_s"].values()) - r["solve_device_s"]
            for r in ctx.rounds]
    return sum(host) * 1e3 / len(host)
