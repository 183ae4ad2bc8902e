"""scheduler, host half: busy time of the ``release.*`` spans (a bound pod
leaving: fine-grained release, node unreserve) inside the window per pod."""

from benchmarks import program_spans


def read(ctx):
    recs = [r for r in program_spans.records(ctx)
            if r["name"].startswith("release.")]
    pods: dict[str, float] = {}
    for r in recs:
        pods[r["name"]] = pods.get(r["name"], 0.0) + r["n"]
    if not pods:
        return None
    return sum(r["busy_s"] for r in recs) * 1e3 / max(pods.values())
