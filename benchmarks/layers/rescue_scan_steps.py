"""solve kernels: the loop steps the round's exact rescue scan took (the
flight record's ``rescue_steps``: the leftover rows that had a feasible node
when the scan started; the standing pods that fit no node are pruned at its
entry and take none), mean over the window's rounds.  Reads ``None`` on a
program whose flight record keeps no such field."""


def read(ctx):
    steps = [r.get("rescue_steps") for r in ctx.rounds]
    if not steps or None in steps:
        return None
    return sum(steps) / len(steps)
