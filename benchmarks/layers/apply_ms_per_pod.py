"""transport (deltasync apply): the benchmark's span around each wave's
``add_pod`` loop, per pod."""


def read(ctx):
    pods = ctx.window_count("wave_apply", "pods")
    return ctx.window_total_s("wave_apply") * 1e3 / pods if pods else None
