"""95th percentile, over every pod offered in the window, of the time from
the client issuing the pod's ``pod_add`` frame to the client holding the
``SOLVE_RESPONSE`` that names it.  A pod not bound when the window closes
counts at the close: it missed."""


def samples_ms(ctx) -> list[float]:
    books = ctx.books
    return sorted((books.bound_at.get(pod, ctx.t_close) - books.sent_at[pod])
                  * 1e3 for pod in books.offered if pod in books.sent_at)


def read(ctx):
    samples = samples_ms(ctx)
    if not samples:
        return None
    # nearest rank: the smallest sample with 95 % of all at or below it
    return samples[max(0, -(-95 * len(samples) // 100) - 1)]
