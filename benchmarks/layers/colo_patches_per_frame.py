"""colocation: patches per pushed frame over the window's ticks.  The
patches are the program's counter ``colocation_patches_total`` after the
window minus before its first tick (``colo_patches_per_tick``'s reading);
the frames are the program's ``colo.push.frame`` calls inside the window,
one ``rpc.call.STATE_PUSH`` each (``colocation_push_frames_total`` counts the
same calls, but no span carries a reading of it before the window).  ``None``
where the program keeps no such counter or sends no such frame (the parent of
PR 33: one call a patch, under ``colo.push``)."""

from benchmarks import program_spans


def read(ctx):
    from koordinator_tpu import metrics

    patches = getattr(metrics, "colocation_patches_total", None)
    before = [c.get("patches_before")
              for *_, c in ctx._window("colo_tick")]
    if patches is None or not before or before[0] is None:
        return None
    _, frames = program_spans.total(
        program_spans.records(ctx),
        lambda r: r["name"] == "rpc.call.STATE_PUSH"
        and r["parent"] == "colo.push.frame")
    return (patches.value() - before[0]) / frames if frames else None
