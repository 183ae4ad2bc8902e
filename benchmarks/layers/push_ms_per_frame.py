"""transport (codec + channel + apply): the benchmark's spans around the
window's ``STATE_PUSH`` calls, per frame."""


def read(ctx):
    frames = ctx.window_count("push", "frames")
    return ctx.window_total_s("push") * 1e3 / frames if frames else None
