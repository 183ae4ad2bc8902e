"""migration: busy time of the program's ``migrate.arbitrate`` spans (sort
the pending jobs, filter by the per-node, per-namespace and per-workload
limits) inside the window, mean per reconcile."""

from benchmarks import program_spans


def read(ctx):
    mine = [r for r in program_spans.records(ctx)
            if r["name"] == "migrate.arbitrate"]
    if not mine:
        return None
    return sum(r["busy_s"] for r in mine) * 1e3 / len(mine)
