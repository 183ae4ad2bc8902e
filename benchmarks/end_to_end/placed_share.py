"""Pods offered in the window that were bound in it, as a share of those
offered.  The standing pods that fit no node are not offered.  Bound pods
are counted from the offered set only, so the share cannot pass 100."""


def read(ctx):
    return 100.0 * ctx.books.bound_in_window / len(ctx.books.offered)
