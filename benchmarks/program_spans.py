"""The program's own spans inside the window: ``koordinator_tpu.timeline``
docs (rounds, cycles and the ingest windows between them) cut to
[``ctx.t_open``, ``ctx.t_close``].

A record is one run of back-to-back spans: ``name``, ``parent`` (the span
open on the same thread), ``thread``, ``n`` members and their ``busy_s``.  A
run that straddles an end of the window is cut pro rata.  A program that
keeps no such records (none of these names, no ``n``) gives an empty list and
the readers return ``None``.  ``ctx.timeline_docs``, where a test sets it,
stands in for the recorder's ring.
"""

from __future__ import annotations


def records(ctx) -> list[dict]:
    docs = getattr(ctx, "timeline_docs", None)
    if docs is None:
        from koordinator_tpu import timeline

        docs = timeline.RECORDER.cycles(64)
    out = []
    for doc in docs:
        for seg in doc.get("segments", ()):
            if "n" not in seg:
                continue
            start, end = doc["start"] + seg["start"], doc["start"] + seg["end"]
            lo, hi = max(start, ctx.t_open), min(end, ctx.t_close)
            if hi <= lo:
                continue
            share = (hi - lo) / (end - start)
            out.append(dict(seg, n=seg["n"] * share,
                            busy_s=seg["busy_s"] * share))
    return out


def total(recs: list[dict], pick) -> tuple[float, float]:
    """(busy seconds, members) over the records ``pick`` takes."""
    mine = [r for r in recs if pick(r)]
    return sum(r["busy_s"] for r in mine), sum(r["n"] for r in mine)
